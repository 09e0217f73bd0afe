"""Device time per round of the ops the program names under its
``fl.mamba`` scope: the Mamba2 layers of the client step (and of the
chunk's one eval), forward, rematerialised forward and backward
(ms/round). None where the program names no such scope."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not ctx["rounds"] or "mamba" not in t.get("scope_s", {}):
        return None
    return 1e3 * t["scope_s"]["mamba"] / ctx["rounds"]
