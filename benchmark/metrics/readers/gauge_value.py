"""A gauge of the program's metrics registry (``hvd.metrics``), by name
(``args.gauge``), the series whose ``axis_size`` label is the cell's chip
count, times ``args.scale``. Nothing to read where the program sets no
such gauge."""


def read(ctx, gauge, scale=1.0):
    from horovod_tpu import metrics
    family = metrics.snapshot().get(gauge)
    chips = str(ctx["workload"]["chips"])
    for series in (family or {}).get("series", ()):
        if series["labels"].get("axis_size") == chips:
            return scale * series["value"]
    return None
