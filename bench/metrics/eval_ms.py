"""Device time per round of the ops the name stack puts in the
eval layer (ms/round)."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not ctx["rounds"] or "eval" not in t["layer_s"]:
        return None
    return 1e3 * t["layer_s"]["eval"] / ctx["rounds"]
