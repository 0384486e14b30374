"""Work counts from shapes: what the algorithm needs, not what a kernel does.

The counts themselves are the architecture's (``benchmark/archs/<arch>.py``
states ``step_flops`` and the work of its kernels); here is what every
architecture shares: the look-up by the configuration's ``arch`` and the
least-seconds rule against the chip's peaks. ``step.mfu_pct`` and
``kernels.flash_roofline`` both read through this file, and an
architecture's file derives both counts from one set of sizes, so the
kernels' count is attention's part of the step's count and the two cannot
disagree. Recomputed operations are not counted.
"""

from . import arch


def step_flops(cfg, sequences, seq_len):
    """FLOPs the forward and backward passes of one step need."""
    return arch.of(cfg).step_flops(cfg, sequences, seq_len)


def flash_work(cfg, sequences, seq_len):
    """{pass: {"flops", "bytes"}} of the flash kernels over one step."""
    return arch.of(cfg).flash_work(cfg, sequences, seq_len)


def least_seconds(work, peaks):
    """Least time the chip could take for ``work`` ({pass: {"flops",
    "bytes"}}): per pass the larger of FLOPs / peak FLOP/s and bytes / peak
    bytes/s, summed over the passes."""
    return sum(max(w["flops"] / peaks["bf16_flops_per_s"],
                   w["bytes"] / peaks["hbm_bytes_per_s"])
               for w in work.values())


def flash_least_seconds(cfg, sequences, seq_len, peaks):
    """Least time the chip could take for the flash kernels' work of one
    step."""
    return least_seconds(flash_work(cfg, sequences, seq_len), peaks)
