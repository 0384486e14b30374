"""Per-chip peak tables and MFU/roofline math.

Methodology (docs/performance.md): MFU is achieved model FLOP/s divided by
the chip's peak dense bf16 FLOP/s — model FLOPs come from
``Compiled.cost_analysis()`` (XLA's per-device estimate) or an explicit
``hvd.set_flops_per_step()`` (the paper-formula route, e.g. ``6·N·B·L``
for transformer training, the convention of the PaLM/MLPerf accounting).
Wire utilization is collective bytes-on-wire per second against the
interconnect roof: the ICI roof within a slice, the DCN roof across
slices/hosts.

Peak numbers are public spec-sheet figures per chip; the CPU row is an
order-of-magnitude placeholder so the CPU tier still produces ratios
(clearly labeled estimates). Override any peak with the env knobs
``HOROVOD_PEAK_TFLOPS`` / ``HOROVOD_PEAK_HBM_GBS`` /
``HOROVOD_PEAK_ICI_GBS`` / ``HOROVOD_PEAK_DCN_GBS``.
"""

import os

# chip -> peaks: dense bf16 TFLOP/s, HBM GB/s, ICI GB/s per chip
# (aggregate across links, one direction), DCN GB/s per host.
PEAKS = {
    # TPU v4: 275 TFLOP/s bf16, 32 GiB HBM2 @ 1228 GB/s, 6 ICI links
    # x 50 GB/s.
    "v4": {"bf16_tflops": 275.0, "hbm_gbs": 1228.0, "ici_gbs": 300.0,
           "dcn_gbs": 25.0},
    # TPU v5e: 197 TFLOP/s bf16, 16 GiB HBM2 @ 819 GB/s, 1600 Gbps ICI.
    "v5e": {"bf16_tflops": 197.0, "hbm_gbs": 819.0, "ici_gbs": 200.0,
            "dcn_gbs": 25.0},
    # TPU v5p: 459 TFLOP/s bf16, 95 GiB HBM @ 2765 GB/s, 4800 Gbps ICI.
    "v5p": {"bf16_tflops": 459.0, "hbm_gbs": 2765.0, "ici_gbs": 600.0,
            "dcn_gbs": 25.0},
    # CPU tier (tests, dry runs): order-of-magnitude placeholder so MFU
    # ratios stay computable — never quote these as hardware truth.
    "cpu": {"bf16_tflops": 0.2, "hbm_gbs": 50.0, "ici_gbs": 10.0,
            "dcn_gbs": 10.0, "estimate": True},
}


# ``device_kind`` as jax reports it -> PEAKS key. "TPU v5 lite" is what a
# v5e answers (observed, PR 21); the other spellings are jax's own
# (jax/_src/mesh_utils.py).
_TPU_KINDS = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5e": "v5e",
    "TPU v5p": "v5p",
}


def detect_chip():
    """PEAKS key of the local backend. Any non-TPU platform is the CPU
    tier; a TPU whose ``device_kind`` is not in the table raises — a chip
    measured against another chip's (or the CPU placeholder's) peaks
    would report a made-up utilization."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return "cpu"
    try:
        return _TPU_KINDS[dev.device_kind]
    except KeyError:
        raise ValueError(
            f"unknown TPU device_kind {dev.device_kind!r}: add its peaks "
            f"to horovod_tpu.profile.roofline.PEAKS/_TPU_KINDS") from None


def chip_peaks(chip=None):
    """Peak table for ``chip`` (default: detected), env overrides
    applied. Returns a fresh dict: ``bf16_tflops``, ``hbm_gbs``,
    ``ici_gbs``, ``dcn_gbs``, ``chip`` (+ ``estimate`` on the CPU row)."""
    chip = chip or detect_chip()
    peaks = dict(PEAKS[chip])
    peaks["chip"] = chip

    def _ovr(env, key):
        v = os.environ.get(env)
        if v:
            try:
                peaks[key] = float(v)
            except ValueError:
                pass

    _ovr("HOROVOD_PEAK_TFLOPS", "bf16_tflops")
    _ovr("HOROVOD_PEAK_HBM_GBS", "hbm_gbs")
    _ovr("HOROVOD_PEAK_ICI_GBS", "ici_gbs")
    _ovr("HOROVOD_PEAK_DCN_GBS", "dcn_gbs")
    return peaks


def cost_from_compiled(compiled):
    """Per-device (FLOPs, bytes-accessed) of one compiled program from
    XLA's cost analysis (``Compiled.cost_analysis()`` is already
    per-device for SPMD programs). ``(None, None)`` when unavailable."""
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        flops = float(cost.get("flops", 0.0))
        nbytes = float(cost.get("bytes accessed", 0.0))
        return (flops if flops > 0 else None,
                nbytes if nbytes > 0 else None)
    except Exception:  # noqa: BLE001
        return None, None


def flops_from_compiled(compiled):
    """FLOPs half of :func:`cost_from_compiled` — callers without a
    bandwidth story (the ledger's MFU) fall back to
    ``hvd.set_flops_per_step()`` on None."""
    return cost_from_compiled(compiled)[0]


def mfu(flops_per_step, step_seconds, peaks=None):
    """Model FLOP/s utilization: achieved TFLOP/s / peak bf16 TFLOP/s.
    Returns ``(mfu_fraction, achieved_tflops)`` or ``(None, None)``."""
    if not flops_per_step or not step_seconds or step_seconds <= 0:
        return None, None
    peaks = peaks or chip_peaks()
    achieved = flops_per_step / step_seconds / 1e12
    peak = peaks.get("bf16_tflops") or 0.0
    return (achieved / peak if peak > 0 else None), achieved


def tier_time_estimate(bytes_by_tier, world_size, num_slices=1, peaks=None):
    """Roofline LOWER-BOUND time for one step's per-link-tier byte totals
    (the static cost model's ``bytes_by_tier``): the ICI total is spread
    over the ``world_size`` chips against the per-chip ICI roof, the DCN
    total over the ``num_slices`` cross-slice links against the DCN roof.
    Returns ``{"ici_s", "dcn_s", "bound", "chip", "estimate"}`` — ``bound``
    names the slower tier (the leg a hierarchical schedule must overlap or
    quantize first). Chip peaks come from :func:`chip_peaks`, so the CPU
    tier's placeholder roofs are flagged ``estimate``."""
    peaks = peaks or chip_peaks()
    world_size = max(int(world_size), 1)
    num_slices = max(int(num_slices), 1)
    ici = float(bytes_by_tier.get("ici", 0) or 0)
    dcn = float(bytes_by_tier.get("dcn", 0) or 0)
    ici_roof = (peaks.get("ici_gbs") or 0.0) * 1e9
    dcn_roof = (peaks.get("dcn_gbs") or 0.0) * 1e9
    t_ici = (ici / world_size / ici_roof) if ici_roof > 0 else None
    t_dcn = (dcn / num_slices / dcn_roof) if dcn_roof > 0 else None
    bound = "dcn" if (t_dcn or 0.0) >= (t_ici or 0.0) else "ici"
    return {"ici_s": t_ici, "dcn_s": t_dcn, "bound": bound,
            "chip": peaks.get("chip"),
            "estimate": bool(peaks.get("estimate"))}


def wire_utilization(bytes_on_wire, step_seconds, peaks=None,
                     cross_host=False):
    """Collective bytes/s against the interconnect roof (ICI within a
    slice, DCN across hosts). Returns ``(fraction, gbytes_per_s)`` or
    ``(None, None)``."""
    if not bytes_on_wire or not step_seconds or step_seconds <= 0:
        return None, None
    peaks = peaks or chip_peaks()
    gbs = bytes_on_wire / step_seconds / 1e9
    roof = peaks.get("dcn_gbs" if cross_host else "ici_gbs") or 0.0
    return (gbs / roof if roof > 0 else None), gbs
