"""Seconds of the spans ``args.span`` of the program's ``run`` trace whose
``args.fun`` is ``args.fun``: the stages of one function's compile
(``compile.trace``, ``compile.lower``, ``compile.backend`` and, under the
last, ``compile.cache_load``; ``horovod_tpu/metrics/instruments.py``
``install_compile_cache_listener`` turns JAX's monitoring events into
them), summed where the function compiled more than once. The store
outlives ``hvd.shutdown``.

With ``args.zero_with`` (another span's name) a function that has that span
and none of ``args.span`` reads 0: a compile that did not come from the
persistent cache spent no time loading from it. Nothing to read where the
function has neither: a program without these spans.
"""


def read(ctx, span, fun, zero_with=None):
    from horovod_tpu import trace
    run_tid = getattr(trace, "run_tid", None)
    record = trace.get(run_tid()) if run_tid is not None else None
    mine = [s for s in (record or {}).get("spans", ())
            if (s.get("args") or {}).get("fun") == fun]
    durations = [s["dur"] for s in mine if s["name"] == span]
    if durations:
        return sum(durations)
    if zero_with is not None and any(s["name"] == zero_with for s in mine):
        return 0.0
    return None
