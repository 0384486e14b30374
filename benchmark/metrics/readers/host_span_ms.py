"""Mean duration, in milliseconds, of the program's host span ``args.span``
in the traced tail: the ``hvd::<span>`` events ``horovod_tpu.trace.span``
leaves in the profiler's trace (``harness/scopes.py``)."""

from benchmark.harness import scopes


def read(ctx, span):
    trace = scopes.of_run(ctx)
    spans = trace.host_spans(span) if trace is not None else []
    if not spans:
        return None
    return 1e-6 * sum(s.end_ns - s.start_ns for s in spans) / len(spans)
