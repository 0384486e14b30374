"""The flash kernels' share of their roofline: least time for their work at
the cell's shapes over their device time in the trace, by kernel name."""

from benchmark.harness import flops

KERNELS = r"hvd_flash_(fwd|bwd_dq|bwd_dkv)"


def read(ctx):
    trace, peaks, w = ctx["trace"], ctx["peaks"], ctx["window"]
    if trace is None or peaks is None:
        return None
    spent = trace.seconds_of(KERNELS)
    if spent <= 0.0:
        return None
    least = flops.flash_least_seconds(
        ctx["cfg"], w["sequences_per_chip"], w["sequence_length"], peaks)
    return 100.0 * least * trace.steps / spent
