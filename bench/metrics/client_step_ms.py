"""Device time per round of the ops the name stack puts in the
client step layer (ms/round)."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not ctx["rounds"] or "client_step" not in t["layer_s"]:
        return None
    return 1e3 * t["layer_s"]["client_step"] / ctx["rounds"]
