"""A host-clock span of the benchmark's set-up, by name (``args.span``)."""


def read(ctx, span):
    return ctx["spans"].get(span)
