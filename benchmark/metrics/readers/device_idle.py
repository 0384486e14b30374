"""1 - union of op intervals on the chip / traced window, mean over the
cell's chips."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s())
