"""Device milliseconds a step of the ops the program put under one phase of
its step (``args.phase``: forward, backward, optimizer, reduce,
bookkeeping; ``harness/scopes.py`` says which ``hvd.*`` scope is which),
mean over the cell's chips. Nothing to read where no op carries the scope:
a program that names nothing, or a phase the cell does not run."""

from benchmark.harness import scopes


def read(ctx, phase):
    trace = scopes.of_run(ctx)
    return None if trace is None else trace.phase_ms_per_step(phase)
