"""Seconds of the set-up span ``args.span`` in the program's ``run`` trace
(``horovod_tpu.trace``: ``import``, ``init`` and its children,
``broadcast_parameters``, ``opt_state_init``), summed where the span was
opened more than once (``init.recorders`` is, before and after the
bootstrap). The store outlives ``hvd.shutdown``. Nothing to read from a
program without the ``run`` trace."""


def read(ctx, span):
    from horovod_tpu import trace
    run_tid = getattr(trace, "run_tid", None)
    record = trace.get(run_tid()) if run_tid is not None else None
    durations = [s["dur"] for s in (record or {}).get("spans", ())
                 if s["name"] == span]
    return sum(durations) if durations else None
