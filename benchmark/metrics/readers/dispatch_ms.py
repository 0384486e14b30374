"""Mean host time per step inside ``shard_batch`` + the compiled step's call
(until it returns), over the untraced window."""


def read(ctx):
    w = ctx["window"]
    if not w["steps"]:
        return None
    return 1e3 * w["dispatch_s"] / w["steps"]
