"""The whole step's share of the chip's bf16 peak: FLOPs the forward and
backward passes need (from shapes, ``harness/flops.py``) over the untraced
window's time. Recomputation does not count."""

from benchmark.harness import flops


def read(ctx):
    w, peaks = ctx["window"], ctx["peaks"]
    if peaks is None or not w["steps"]:
        return None
    per_chip = flops.step_flops(ctx["cfg"], w["sequences_per_chip"],
                                w["sequence_length"])
    return 100.0 * per_chip * w["steps"] / w["seconds"] \
        / peaks["bf16_flops_per_s"]
