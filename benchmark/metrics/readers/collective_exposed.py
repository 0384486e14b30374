"""Per step, time in collective ops during which no other op runs on the
chip; mean over chips and traced steps. Nothing to read without
collectives in the trace."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    exposed = trace.collective_exposed_s()
    if exposed <= 0.0:
        return None
    return 1e3 * exposed / trace.steps
