"""Device time per round of the ops no other layer claims: sparsify,
aggregate and apply, batch sampling and fading (ms/round)."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not ctx["rounds"] or "rest" not in t["layer_s"]:
        return None
    return 1e3 * t["layer_s"]["rest"] / ctx["rounds"]
