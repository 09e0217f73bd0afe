"""Device time per round of the ops the program names under its
``fl.shared_block`` scope: the shared attention blocks' invocations and
their ``linear``, in the client step (and the chunk's one eval),
forward, rematerialised forward and backward (ms/round). None where the
program names no such scope."""


def read(ctx):
    t = ctx["trace"]
    if (t is None or not ctx["rounds"]
            or "shared_block" not in t.get("scope_s", {})):
        return None
    return 1e3 * t["scope_s"]["shared_block"] / ctx["rounds"]
