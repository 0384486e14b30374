#!/usr/bin/env python
"""Headline benchmark: ResNet-50 synthetic data-parallel training throughput.

Mirrors the reference's benchmark procedure (reference:
docs/benchmarks.rst:15-64 — tf_cnn_benchmarks with synthetic ImageNet data,
images/sec): one full training step (fwd + bwd + fused gradient allreduce +
SGD update) on synthetic 224x224x3 batches, bf16 activations.

Baseline for ``vs_baseline``: the reference's only published absolute number,
1656.82 images/sec on 16 Pascal GPUs (ResNet-101, batch 64/GPU,
docs/benchmarks.rst:28-42) -> 103.55 images/sec/chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

_T0 = time.perf_counter()

# Partial-progress side file: one JSONL record per phase mark, flushed per
# line, so a run that dies mid-way still leaves parseable evidence of how
# far it got and when. "" disables.
_PROGRESS_PATH = os.environ.get("HVD_BENCH_PROGRESS_FILE",
                                "bench_progress.jsonl")


def _progress_record(phase, **extra):
    if not _PROGRESS_PATH:
        return
    try:
        rec = {"ts": round(time.time(), 3),
               "elapsed_s": round(time.perf_counter() - _T0, 3),
               "model": os.environ.get("HVD_BENCH_MODEL", "resnet50"),
               "phase": phase}
        rec.update(extra)
        # Flight-recorder evidence rides every progress line: even when
        # the run never reaches a BENCH record, each phase mark says how
        # far the collective sequence got and what the steps cost.
        fsum, _ = _flight_summary_field()
        if fsum is not None:
            rec["flight"] = fsum
        # Step-profiler evidence too: per-phase attribution + MFU so far.
        ssum, _ = _step_report_field()
        if ssum is not None:
            rec["step_report"] = ssum
        # Cluster-health evidence: job-view health counts + unhealthy
        # ranks, so a wedged phase names its suspect in the stream.
        csum, _ = _cluster_snapshot_field()
        if csum is not None:
            rec["cluster_snapshot"] = csum
        # Goodput evidence: was the run productive up to this phase mark
        # (and if not, which badput category ate the wall)?
        gsum, _ = _goodput_summary_field()
        if gsum is not None:
            rec["goodput"] = gsum
        with open(_PROGRESS_PATH, "a") as f:
            f.write(json.dumps(rec) + "\n")
    except OSError:
        pass                      # evidence must never fail the bench


def _mark(msg):
    print(f"# [{time.perf_counter() - _T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)
    _progress_record(msg)
    _watchdog_kick()              # progress resets the inactivity guard


def _flash_default():
    """Pallas flash attention default-on for every transformer bench;
    HVD_BENCH_FLASH=0 opts out to plain XLA attention."""
    return os.environ.get("HVD_BENCH_FLASH", "1") == "1"


def _remat_default():
    """HVD_BENCH_REMAT=1: jax.checkpoint every transformer block —
    activation memory for FLOPs, the knob for bigger per-chip batches
    (MFU) and longer contexts."""
    return os.environ.get("HVD_BENCH_REMAT", "0") == "1"


def _roofline_peaks():
    """Per-chip peaks for the roofline: ONE source of truth
    (horovod_tpu.profile.roofline's chip-detected table, the same one the
    step profiler's MFU uses) with the historical HVD_BENCH_PEAK_* env
    overrides kept on top."""
    from horovod_tpu.profile import roofline as prof_roofline
    peaks = prof_roofline.chip_peaks()
    return (float(os.environ.get("HVD_BENCH_PEAK_TFLOPS",
                                 peaks["bf16_tflops"])),
            float(os.environ.get("HVD_BENCH_PEAK_GBS",
                                 peaks["hbm_gbs"])))


def _roofline(compiled, dt_per_step, n_chips):
    """XLA-cost-analysis roofline for one compiled train step: measured
    TFLOP/s vs the compute roof AND the bandwidth roof, so a low MFU is
    attributable (bandwidth-bound vs badly-scheduled) instead of argued.
    Numbers go to stderr; the single stdout JSON line stays the driver
    contract."""
    del n_chips  # XLA cost_analysis is already PER-DEVICE for SPMD programs
    from horovod_tpu.profile import roofline as prof_roofline
    flops, bytes_acc = prof_roofline.cost_from_compiled(compiled)
    if flops is None:
        _mark("roofline: cost_analysis unavailable")
        return
    bytes_acc = bytes_acc or 0.0
    if dt_per_step <= 0:
        return
    peak_tflops, peak_gbs = _roofline_peaks()
    achieved = flops / dt_per_step / 1e12
    intensity = flops / max(bytes_acc, 1.0)
    # time lower bounds from each roof
    t_compute = flops / (peak_tflops * 1e12)
    t_memory = bytes_acc / (peak_gbs * 1e9)
    bound = "memory" if t_memory > t_compute else "compute"
    _mark(f"roofline: {flops / 1e9:.1f} GFLOP/step/chip, "
          f"{bytes_acc / 1e9:.2f} GB accessed/step/chip, "
          f"intensity {intensity:.0f} FLOP/B")
    _mark(f"roofline: achieved {achieved:.1f} TFLOP/s/chip = "
          f"{100 * achieved / peak_tflops:.1f}% of peak; {bound}-bound "
          f"(compute roof {1e3 * t_compute:.2f} ms vs memory roof "
          f"{1e3 * t_memory:.2f} ms vs measured "
          f"{1e3 * dt_per_step:.2f} ms/step)")
    _mark(f"roofline: best-case {bound}-bound step would hit "
          f"{flops / max(t_compute, t_memory) / 1e12:.1f} TFLOP/s "
          f"({100 * max(t_compute, t_memory) / dt_per_step:.0f}% "
          f"roof utilization at the measured time)")


def _timed_steps(step, state, data, warmup=2):
    """Shared timing protocol for every benchmark: place the state
    replicated on every chip and the batch split over the mesh (left on the
    default device, the batch is re-scattered from chip 0 every step),
    AOT-compile the step (one compile, shared with the roofline's cost
    analysis), `warmup` synced steps, then HVD_BENCH_ITERS timed steps
    closed by one block_until_ready.  Returns (iters, seconds)."""
    import horovod_tpu as hvd
    from horovod_tpu.parallel import shard_batch
    from jax.sharding import NamedSharding, PartitionSpec
    mesh = hvd.global_process_set.mesh
    state = jax.device_put(state, NamedSharding(mesh, PartitionSpec()))
    data = shard_batch(data, mesh)
    run = step.lower(state, data).compile()
    _mark("step compiled (AOT)")
    # Feed the step profiler: FLOPs/step from the compiled program's cost
    # analysis (MFU per step record) and a step marker per iteration so
    # every BENCH record carries a step_report summary. Markers bracket
    # DISPATCH cadence — the trailing sync means the last window absorbs
    # the device lag, which the summary's p50 ignores.
    try:
        from horovod_tpu.profile import roofline as prof_roofline
        flops = prof_roofline.flops_from_compiled(run)
        if flops:
            hvd.set_flops_per_step(flops, source="cost_analysis")
        hvd.step_marker(0)
        _bench_step = hvd.step_marker
    except Exception:  # noqa: BLE001 — profiling must not fail the bench
        def _bench_step(i):
            return None
    for i in range(warmup):
        state, loss = run(state, data)
        float(loss)
        _mark(f"warmup step {i} done")
        _bench_step(i + 1)
    iters = int(os.environ.get("HVD_BENCH_ITERS", "20"))
    t0 = time.perf_counter()
    for k in range(iters):
        state, loss = run(state, data)
        _bench_step(warmup + k + 1)
    jax.block_until_ready((state, loss))
    dt = time.perf_counter() - t0
    _mark(f"{iters} timed steps in {dt:.2f}s")
    try:
        _roofline(run, dt / iters, jax.device_count())
    except Exception as e:  # noqa: BLE001
        _mark(f"roofline skipped: {e}")
    return iters, dt


_WATCHDOG = None
_WATCHDOG_SECS = None


def _metrics_snapshot_field():
    """The metrics-registry ride-along for every BENCH record: collective/
    fusion/KV counters captured even when the run fails. Returns
    ``(snapshot_or_None, reason_or_None)`` — ``None`` with a reason when
    the registry is unavailable or empty-by-failure."""
    try:
        import horovod_tpu as hvd
        # No is_initialized() gate: the registry is process-global and
        # accrues control-plane/elastic counters DURING a failing init.
        return hvd.metrics_snapshot(), None
    except Exception as e:  # noqa: BLE001 — telemetry must not fail bench
        return None, (str(e).splitlines() or ["?"])[0][:160]


def _flight_summary_field():
    """The flight-recorder ride-along: event counts by kind, per-set max
    collective seq, step-span stats. Like the metrics snapshot, this
    accrues during a FAILING run too — a partial bench still says how many
    collectives dispatched, where the sequence stopped, and what the last
    steps cost.
    Returns ``(summary_or_None, reason_or_None)``."""
    try:
        from horovod_tpu.flight import recorder
        return recorder.summary(), None
    except Exception as e:  # noqa: BLE001 — telemetry must not fail bench
        return None, (str(e).splitlines() or ["?"])[0][:160]


def _step_report_field():
    """The step-profiler ride-along: per-phase attribution means, step
    wall p50, and the MFU estimate (flops from the compiled step's cost
    analysis). Accrues during a failing run too — a partial bench still
    says where its steps' time went.
    Returns ``(summary_or_None, reason_or_None)``."""
    try:
        from horovod_tpu.profile import ledger
        return ledger.step_report_summary(), None
    except Exception as e:  # noqa: BLE001 — telemetry must not fail bench
        return None, (str(e).splitlines() or ["?"])[0][:160]


def _cluster_snapshot_field():
    """The telemetry-plane ride-along: per-rank health states + per-slice
    digest counts from the job view (local-only view on single-process
    benches — cluster_snapshot() never returns None). A wedged run then
    still records WHICH rank/slice the plane last saw unhealthy.
    Compacted: health counts, per-slice leader/digest counts, progress,
    and only the non-healthy ranks in full.
    Returns ``(snapshot_or_None, reason_or_None)``."""
    try:
        import horovod_tpu as hvd
        view = hvd.cluster_snapshot()
        return {
            "gen": view.get("gen"),
            "world": view.get("world"),
            "num_slices": view.get("num_slices"),
            "local_only": view.get("local_only", False),
            "counts": view.get("counts"),
            "progress": view.get("progress"),
            "slices": {
                sid: {"leader": s.get("leader"),
                      "digests": s.get("digests")}
                for sid, s in (view.get("slices") or {}).items()},
            "unhealthy": {
                r: s for r, s in (view.get("health") or {}).items()
                if s.get("state") != "healthy"},
            "events": (view.get("events") or [])[-8:],
        }, None
    except Exception as e:  # noqa: BLE001 — telemetry must not fail bench
        return None, (str(e).splitlines() or ["?"])[0][:160]


def _goodput_summary_field():
    """The goodput-ledger ride-along: the wall-clock decomposition
    (goodput ratio + per-category badput seconds + conservation error),
    so every BENCH record says not just how fast the steps were but how
    much of the run's wall was productive at all. ``None`` (with a
    reason) when accounting is off.
    Returns ``(summary_or_None, reason_or_None)``."""
    try:
        from horovod_tpu.goodput import ledger as goodput_ledger
        snap = goodput_ledger.snapshot()
        if not snap.get("enabled"):
            return None, "goodput accounting off (HOROVOD_GOODPUT=0)"
        return snap, None
    except Exception as e:  # noqa: BLE001 — telemetry must not fail bench
        return None, (str(e).splitlines() or ["?"])[0][:160]


def _with_metrics(record):
    snap, reason = _metrics_snapshot_field()
    record["metrics_snapshot"] = snap
    if snap is None:
        record["metrics_snapshot_reason"] = reason
    fsum, freason = _flight_summary_field()
    record["flight_summary"] = fsum
    if fsum is None:
        record["flight_summary_reason"] = freason
    ssum, sreason = _step_report_field()
    record["step_report"] = ssum
    if ssum is None:
        record["step_report_reason"] = sreason
    csum, creason = _cluster_snapshot_field()
    record["cluster_snapshot"] = csum
    if csum is None:
        record["cluster_snapshot_reason"] = creason
    gsum, greason = _goodput_summary_field()
    record["goodput"] = gsum
    if gsum is None:
        record["goodput_reason"] = greason
    else:
        # Durable evidence: when a run journal is armed (rank 0 +
        # HOROVOD_RUN_HISTORY_DIR) the BENCH record rides into the
        # cross-run history too — `goodput.report` then regresses perf
        # and efficiency from the same file.
        try:
            from horovod_tpu.goodput import history as _history
            _history.journal_append(
                "bench", record={k: record.get(k) for k in
                                 ("metric", "value", "unit",
                                  "vs_baseline")},
                goodput=gsum)
        except Exception:  # noqa: BLE001
            pass
    return record


def _device():
    """The device every record names: a number without it cannot be told
    from a CPU run's."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def _emit_failure(metric, unit, error):
    """The ONE parseable failure-record shape (shared by the watchdog and
    the __main__ handler so the driver's parser sees one schema)."""
    print(json.dumps(_with_metrics({
        "metric": metric, "value": 0.0, "unit": unit, "vs_baseline": 0.0,
        "error": error,
    })), flush=True)


def _arm_watchdog(seconds, metric, unit):
    """INACTIVITY guard for mid-run hangs: a device get can block forever,
    where no Python exception (or signal handler — the interpreter never
    regains control) will fire. A daemon timer prints the parseable
    failure JSON and exits hard. Every progress line
    (:func:`_mark`) re-arms it, so the deadline bounds silence, not total
    runtime — long contexts / many iters stay alive as long as they keep
    marking."""
    global _WATCHDOG_SECS
    _WATCHDOG_SECS = (seconds, metric, unit)
    _watchdog_kick()


def _watchdog_kick():
    import threading

    global _WATCHDOG
    if _WATCHDOG_SECS is None:
        return
    seconds, metric, unit = _WATCHDOG_SECS
    if _WATCHDOG is not None:
        _WATCHDOG.cancel()

    def boom():
        _emit_failure(metric, unit,
                      f"bench watchdog: no progress for {seconds:.0f}s — "
                      f"device hang mid-run")
        os._exit(1)

    _WATCHDOG = threading.Timer(seconds, boom)
    _WATCHDOG.daemon = True
    _WATCHDOG.start()


def _watchdog_cancel():
    global _WATCHDOG, _WATCHDOG_SECS
    _WATCHDOG_SECS = None
    if _WATCHDOG is not None:
        _WATCHDOG.cancel()
        _WATCHDOG = None


def _emit(metric, value, unit, vs_baseline):
    _watchdog_cancel()
    device = _device()
    print(json.dumps(_with_metrics({
        "metric": metric,
        "value": value,
        "unit": unit,
        "vs_baseline": vs_baseline,
        "platform": device["platform"],
        "device": device,
    })))


def _bench_bert(hvd):
    """BERT-Large MLM+NSP fine-tune step, seq 128 (BASELINE tracked config:
    'BERT-Large fine-tune with tensor fusion'; reference procedure analog of
    docs/benchmarks.rst real-model mode). Reports sequences/sec/chip."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models.bert import BertConfig, BertForPreTraining
    from horovod_tpu.optim import DistributedOptimizer
    from horovod_tpu.parallel import TrainState, make_train_step

    n = hvd.size()
    mesh = hvd.global_process_set.mesh
    seq = int(os.environ.get("HVD_BENCH_SEQ", "128"))
    per_chip = int(os.environ.get("HVD_BENCH_BATCH", "32"))
    batch = per_chip * n
    # No padding in the synthetic batch and dropout is off under
    # deterministic apply, so flash engages.
    cfg = BertConfig.large(use_flash=_flash_default(),
                           remat=_remat_default())
    model = BertForPreTraining(cfg)

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    nsp = jnp.asarray(rng.integers(0, 2, (batch,)), jnp.int32)

    variables = jax.jit(model.init)(jax.random.PRNGKey(0), ids[:1])
    _mark("bert init done")
    opt = DistributedOptimizer(optax.adamw(1e-5),
                               compression=_compression())

    def loss_fn(p, b):
        mlm_logits, nsp_logits = model.apply({"params": p}, b["ids"])
        mlm = optax.softmax_cross_entropy_with_integer_labels(
            mlm_logits, b["mlm"]).mean()
        nsp_l = optax.softmax_cross_entropy_with_integer_labels(
            nsp_logits, b["nsp"]).mean()
        return mlm + nsp_l

    step = make_train_step(loss_fn, opt, mesh, donate=True)
    state = TrainState.create(variables["params"], opt)
    iters, dt = _timed_steps(step, state, {"ids": ids, "mlm": labels,
                                           "nsp": nsp})
    # vs_baseline 0.0: the reference publishes no absolute BERT number.
    _emit("bert_large_seqs_per_sec_per_chip",
          round(batch * iters / dt / n, 2), "sequences/sec/chip", 0.0)


def _bench_lm(hvd, label, metric, model, init_args, batch_dict, loss_fn,
              tokens_per_step):
    """Shared scaffold for the LM benches (GPT/LLaMA/T5): jitted init,
    fused DistributedOptimizer(adamw) step, timed steps, ONE JSON line in
    tokens/sec/chip. vs_baseline 0.0 throughout: the reference publishes
    no LM numbers."""
    from horovod_tpu.optim import DistributedOptimizer
    from horovod_tpu.parallel import TrainState, make_train_step

    n = hvd.size()
    mesh = hvd.global_process_set.mesh
    variables = jax.jit(model.init)(jax.random.PRNGKey(0), *init_args)
    _mark(f"{label} init done")
    opt = DistributedOptimizer(optax.adamw(1e-4),
                               compression=_compression())
    step = make_train_step(loss_fn, opt, mesh, donate=True)
    state = TrainState.create(variables["params"], opt)
    iters, dt = _timed_steps(step, state, batch_dict)
    _emit(metric, round(tokens_per_step * iters / dt / n, 1),
          "tokens/sec/chip", 0.0)


def _lm_shapes(default_seq, default_batch, n):
    seq = int(os.environ.get("HVD_BENCH_SEQ", str(default_seq)))
    per_chip = int(os.environ.get("HVD_BENCH_BATCH", str(default_batch)))
    return seq, per_chip * n


def _next_token_loss(model, key="ids"):
    """Next-token CE. HVD_BENCH_CHUNKED_XENT=1 switches to the chunked
    head+loss (optim/losses.py): the (B, L, V) fp32 logits tensor — the
    single largest HBM term of LM training — never materializes."""
    if os.environ.get("HVD_BENCH_CHUNKED_XENT", "0") == "1":
        import functools
        import math

        from horovod_tpu.models.gpt import GPT, GPTHead
        from horovod_tpu.models.llama import Llama, LlamaHead
        from horovod_tpu.optim import next_token_xent_chunked
        from horovod_tpu.parallel import next_token_labels

        heads = {GPT: GPTHead, Llama: LlamaHead}
        if type(model) not in heads:
            raise ValueError(
                f"HVD_BENCH_CHUNKED_XENT supports {list(heads)}, got "
                f"{type(model).__name__}")
        head = heads[type(model)](model.config)

        def loss_fn(p, b):
            ids = b[key]
            hidden = model.apply({"params": p}, ids, features_only=True)
            labels = next_token_labels(ids, axis_name=None)
            chunk = math.gcd(ids.shape[1], 128) \
                if ids.shape[1] % 128 else 128
            return next_token_xent_chunked(
                functools.partial(head.apply, {"params": p["head"]}),
                hidden, labels, chunk=chunk)

        return loss_fn

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b[key])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1].astype(jnp.float32), b[key][:, 1:]).mean()

    return loss_fn


def _bench_gpt(hvd):
    """GPT-2-small (124M) causal-LM training step, seq 1024 — the long-
    context/transformer headline alongside ResNet (conv) and BERT (encoder).
    Reports tokens/sec/chip."""
    from horovod_tpu.models.gpt import GPT, GPTConfig

    seq, batch = _lm_shapes(1024, 8, hvd.size())
    # Tiled Pallas flash attention (ops/pallas/flash_attention.py) is the
    # default: O(seq) memory and measured faster than plain attention at
    # every context length on v5e (101.7k vs 75.8k tok/s at seq 1024;
    # 75.3k vs 19.0k at 4k). HVD_BENCH_FLASH=0 falls back to plain XLA
    # attention; HVD_BENCH_SEQ stretches the context (16k+ fits one chip).
    cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                    num_heads=12, intermediate_size=3072,
                    max_position_embeddings=seq, dtype=jnp.bfloat16,
                    tp_axis=None, ep_axis=None,
                    use_flash=_flash_default(), remat=_remat_default())
    model = GPT(cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq)), jnp.int32)
    _bench_lm(hvd, "gpt", "gpt2_small_tokens_per_sec_per_chip", model,
              (ids[:1],), {"ids": ids}, _next_token_loss(model),
              batch * seq)


def _bench_llama(hvd):
    """LLaMA-family causal-LM step (RMSNorm + RoPE + SwiGLU + GQA,
    models/llama.py) at the ~400M ``LlamaConfig.bench`` shapes, bf16,
    flash attention by default. Reports tokens/sec/chip (no reference
    number exists)."""
    from horovod_tpu.models import Llama, LlamaConfig

    seq, batch = _lm_shapes(1024, 8, hvd.size())
    cfg = LlamaConfig.bench(max_position_embeddings=seq, dtype=jnp.bfloat16,
                            tp_axis=None, use_flash=_flash_default(),
                            remat=_remat_default())
    model = Llama(cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq)), jnp.int32)
    _bench_lm(hvd, "llama", "llama_400m_tokens_per_sec_per_chip", model,
              (ids[:1],), {"ids": ids}, _next_token_loss(model),
              batch * seq)


def _bench_t5(hvd):
    """T5-small-shaped encoder-decoder step (relative position biases +
    cross-attention, models/t5.py), bf16, seq 512->512, adamw, fused
    allreduce. Reports tokens/sec/chip over decoder tokens (no reference
    number exists)."""
    from horovod_tpu.models import T5, T5Config

    seq, batch = _lm_shapes(512, 16, hvd.size())
    cfg = T5Config(vocab_size=32128, hidden_size=512, num_layers=6,
                   num_heads=8, intermediate_size=1024,
                   dtype=jnp.bfloat16, tp_axis=None)
    model = T5(cfg)
    rng = np.random.default_rng(0)
    src = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                      jnp.int32)
    tgt = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                      jnp.int32)

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["src"], b["tgt"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1].astype(jnp.float32), b["tgt"][:, 1:]).mean()

    _bench_lm(hvd, "t5", "t5_small_tokens_per_sec_per_chip", model,
              (src[:1], tgt[:1]), {"src": src, "tgt": tgt}, loss_fn,
              batch * seq)


def _bench_vit(hvd):
    """ViT-B/16 ImageNet-shape training step, bf16, flash attention by
    default (196 patches pad to 256-row blocks inside the kernels;
    HVD_BENCH_FLASH=0 for plain XLA attention).
    Reports images/sec/chip (no reference number exists)."""
    from horovod_tpu.models import ViT, ViTConfig
    from horovod_tpu.optim import DistributedOptimizer
    from horovod_tpu.parallel import TrainState, make_train_step

    n = hvd.size()
    mesh = hvd.global_process_set.mesh
    per_chip = int(os.environ.get("HVD_BENCH_BATCH", "128"))
    batch = per_chip * n
    cfg = ViTConfig.base(dtype=jnp.bfloat16, use_flash=_flash_default(),
                         remat=_remat_default())
    model = ViT(cfg)
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.standard_normal((batch, 224, 224, 3)),
                         jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, 1000, (batch,)), jnp.int32)
    variables = jax.jit(model.init)(jax.random.PRNGKey(0), images[:1])
    _mark("vit init done")
    opt = DistributedOptimizer(optax.adamw(1e-4))

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, b["y"]).mean()

    step = make_train_step(loss_fn, opt, mesh, donate=True)
    state = TrainState.create(variables["params"], opt)
    iters, dt = _timed_steps(step, state, {"x": images, "y": labels})
    _emit("vit_b16_images_per_sec_per_chip",
          round(batch * iters / dt / n, 2), "images/sec/chip", 0.0)


# The reference's headline benchmark trio is ResNet-101 / Inception V3 /
# VGG-16 (reference: docs/benchmarks.rst:12-13,28-42) with ResNet-50 the
# BASELINE.md tracked flagship.  name -> (model factory kwargs name, image
# side, default per-chip batch, vs-baseline images/sec/chip or None).
# 103.55 = 1656.82/16, the reference's one absolute number (ResNet-101,
# batch 64/GPU); ResNet-50 is benchmarked against it as the tracked config.
_IMAGE_MODELS = {
    "resnet50": ("ResNet50", 224, 256, 1656.82 / 16.0),
    "resnet101": ("ResNet101", 224, 64, 1656.82 / 16.0),
    "inception3": ("InceptionV3", 299, 64, None),
    "vgg16": ("VGG16", 224, 64, None),
}


def _bench_image(hvd, name):
    import horovod_tpu.models as zoo
    from horovod_tpu.optim import DistributedOptimizer
    from horovod_tpu.parallel import TrainState, make_train_step

    factory, side, default_batch, baseline = _IMAGE_MODELS[name]
    n = hvd.size()
    mesh = hvd.global_process_set.mesh
    per_chip_batch = int(os.environ.get("HVD_BENCH_BATCH",
                                        str(default_batch)))
    batch = per_chip_batch * n
    # dropout_rate=0 where the model has a dropout head (VGG/Inception):
    # throughput-neutral and keeps the train step rng-free.
    kwargs = {"num_classes": 1000, "dtype": jnp.bfloat16, "train": True}
    if factory in ("VGG16", "InceptionV3"):
        kwargs["dropout_rate"] = 0.0
    if factory.startswith("ResNet") and \
            os.environ.get("HVD_BENCH_S2D", "0") == "1":
        # MLPerf-style space-to-depth stem (models/resnet.py): feeds the
        # MXU 12 input channels instead of 3 on the stem conv.
        kwargs["stem"] = "space_to_depth"
    model = getattr(zoo, factory)(**kwargs)

    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.standard_normal((batch, side, side, 3)),
                         jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, 1000, (batch,)), jnp.int32)

    variables = jax.jit(model.init)(jax.random.PRNGKey(0), images[:1])
    _mark(f"{name} init done")
    params = variables["params"]
    batch_stats = variables.get("batch_stats")

    opt = DistributedOptimizer(
        optax.sgd(0.1, momentum=0.9),
        compression=_compression())

    if batch_stats is not None:
        def loss_fn(p, b, extra):
            logits, updates = model.apply(
                {"params": p, "batch_stats": extra}, b["x"],
                mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, b["y"]).mean()
            return loss, updates["batch_stats"]

        step = make_train_step(loss_fn, opt, mesh, has_aux=True, donate=True)
        state = TrainState.create(params, opt, extra=batch_stats)
    else:
        def loss_fn(p, b):
            logits = model.apply({"params": p}, b["x"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, b["y"]).mean()

        step = make_train_step(loss_fn, opt, mesh, donate=True)
        state = TrainState.create(params, opt)

    iters, dt = _timed_steps(step, state, {"x": images, "y": labels})
    per_chip = batch * iters / dt / n
    _emit(f"{name}_images_per_sec_per_chip", round(per_chip, 2),
          "images/sec/chip",
          round(per_chip / baseline, 3) if baseline else 0.0)


def _static_cost_record(hvd, elems, n, measured):
    """The hvdcost ride-along for the wire sweep: price the largest
    rung's allreduce with the STATIC per-link-tier cost model
    (analysis/cost.py) and record predicted per-tier bytes next to the
    measured `wire_bytes_total` delta each leg actually put on the wire —
    the static-vs-runtime cross-check as bench evidence on the
    HVD_BENCH_PROGRESS_FILE channel. ``measured`` maps wire leg ->
    measured bytes/op from the sweep."""
    try:
        from horovod_tpu.analysis import cost as an_cost
        from horovod_tpu.analysis.program import check_program
        from horovod_tpu.common.config import Config

        x = np.zeros((n, elems), np.float32)

        def step(x):
            return hvd.allreduce(x, op=hvd.Sum)

        rec = {"payload_mb": round(x.nbytes / 2**20, 2), "world": n}
        for leg, wire in (("float32", ""), ("int8", "int8")):
            cfg = Config(wire_dtype=wire)
            rep = check_program(step, (x,), world_size=n, config=cfg)
            # use_registry=False: counterfactual pricing against cfg
            # alone — the sweep's own registry pins must not leak in.
            cr = an_cost.cost_report(rep, config=cfg, use_registry=False)
            predicted = float(sum(cr.bytes_by_dtype.values()))
            got = measured.get(leg)
            rec[leg] = {
                "bytes_by_tier": dict(cr.bytes_by_tier),
                "predicted_wire_bytes": predicted,
                "measured_wire_bytes": got,
                "delta": (got - predicted) if got is not None else None,
            }
        rec["num_slices"] = cr.num_slices
        _progress_record("static_cost", static_cost=rec)
        _mark(f"static_cost: int8 predicted "
              f"{rec['int8']['predicted_wire_bytes']:.0f}B "
              f"(ici={rec['int8']['bytes_by_tier']['ici']} "
              f"dcn={rec['int8']['bytes_by_tier']['dcn']}) vs measured "
              f"{rec['int8']['measured_wire_bytes']}")
    except Exception as e:  # noqa: BLE001 — evidence must not fail bench
        _progress_record("static_cost", error=str(e)[:160])


def _bench_wire_sweep(hvd):
    """Wire-dtype sweep: the SAME payload ladder through the eager
    allreduce at fp32 / bf16-cast(fused) / int8 wire, reporting per-leg
    dispatch time and the `wire_bytes_total` delta each leg put on the
    wire — the provable off-chip evidence for the quantized tier
    (docs/performance.md "Quantized wire tier"). Every (payload, dtype)
    cell lands as a labeled `wire_sweep` record on the
    HVD_BENCH_PROGRESS_FILE channel; the final BENCH record carries the
    int8-vs-fp32 byte ratio on the largest rung."""
    from horovod_tpu.metrics import instruments as ins
    from horovod_tpu.ops import fusion, wire

    n = hvd.size()
    iters = int(os.environ.get("HVD_BENCH_ITERS", "10"))
    # Per-rank element ladder (global payload = n * elems * 4 B).
    ladder = [n * 1024, 128 * 1024, 1024 * 1024]
    rng = np.random.default_rng(0)

    def wire_bytes(dtype):
        # summed across the tier label (the counter is {dtype, tier})
        snap = ins.get_registry().snapshot()
        return sum(
            s["value"]
            for s in snap.get("wire_bytes_total", {}).get("series", ())
            if s["labels"].get("dtype") == dtype)

    rt = fusion.get_runtime()
    results = {}
    ratio_largest = 0.0
    for elems in ladder:
        x = jnp.asarray(rng.standard_normal((n, elems)), jnp.float32)
        payload_mb = x.nbytes / 2**20
        for leg in ("float32", "bfloat16", "int8"):
            # float32/int8 ride the eager sync path (registry-steered);
            # bfloat16 is a fused-bucket cast, so that leg rides the
            # async fusion runtime where the cast applies.
            fused = leg == "bfloat16"
            label = leg
            hvd.set_wire_dtype("" if leg == "float32" else leg)
            prev_rt_wire = rt.wire_dtype
            if fused:
                rt.wire_dtype = jnp.bfloat16

            def dispatch():
                if fused:
                    return hvd.allreduce_async(
                        x, op=hvd.Sum, name="wire_sweep").synchronize()
                return hvd.allreduce(x, op=hvd.Sum)

            try:
                jax.block_until_ready(dispatch())      # warm/compile
                b0 = wire_bytes(label)
                t0 = time.perf_counter()
                for _ in range(iters):
                    out = dispatch()
                jax.block_until_ready(out)
                dt = (time.perf_counter() - t0) / iters
                delta = wire_bytes(label) - b0
            finally:
                rt.wire_dtype = prev_rt_wire
                hvd.set_wire_dtype("")
            rec = {"payload_mb": round(payload_mb, 2), "wire": leg,
                   "us_per_op": round(dt * 1e6, 1),
                   "wire_bytes_per_op": delta / max(iters, 1),
                   "path": "fused" if fused else "eager"}
            results[(elems, leg)] = rec
            _progress_record("wire_sweep", **rec)
            _mark(f"wire_sweep {payload_mb:.1f}MB {leg}: "
                  f"{dt * 1e6:.0f}us/op, "
                  f"{delta / max(iters, 1) / 2**20:.2f} MB on wire")
        fp32_b = results[(elems, "float32")]["wire_bytes_per_op"]
        int8_b = results[(elems, "int8")]["wire_bytes_per_op"]
        if fp32_b:
            ratio_largest = int8_b / fp32_b
    largest = ladder[-1]
    _static_cost_record(hvd, largest, n, {
        leg: results[(largest, leg)]["wire_bytes_per_op"]
        for leg in ("float32", "int8")})
    wire.reset_error_feedback()
    _emit("wire_sweep_int8_bytes_ratio", round(ratio_largest, 4),
          "int8/fp32 bytes-on-wire ratio (largest rung; <0.3 = the "
          "quantized tier's contract)", 0.0)


def _hierarchy_static_cost(hvd, elems, n, slices, measured):
    """The hvdcost ride-along for the hierarchy sweep: price the largest
    rung's allreduce flat AND hierarchically (counterfactual pricing —
    use_registry=False so the sweep's own strategy/wire pins don't leak
    in) and record the per-tier prediction next to the measured
    `wire_bytes_total{tier}` deltas each leg put on the wire."""
    try:
        from horovod_tpu.analysis import cost as an_cost
        from horovod_tpu.analysis.program import check_program
        from horovod_tpu.common.config import Config

        x = np.zeros((n, elems), np.float32)

        def step(x):
            return hvd.allreduce(x, op=hvd.Sum)

        rec = {"payload_mb": round(x.nbytes / 2**20, 2), "world": n,
               "num_slices": slices}
        legs = (("flat", Config()),
                ("hier", Config(hierarchical_dispatch=True)),
                ("hier_int8", Config(hierarchical_dispatch=True,
                                     wire_dtype_dcn="int8")))
        for leg, cfg in legs:
            rep = check_program(step, (x,), world_size=n, config=cfg)
            cr = an_cost.cost_report(rep, config=cfg, num_slices=slices,
                                     use_registry=False)
            got = measured.get(leg)
            predicted = dict(cr.runtime_bytes_by_tier)
            rec[leg] = {
                "predicted_bytes_by_tier": predicted,
                "measured_bytes_by_tier": got,
                "delta_dcn": (got["dcn"] - predicted["dcn"])
                if got else None,
            }
        _progress_record("static_cost", static_cost=rec)
        _mark(f"static_cost hierarchy: hier_int8 predicted "
              f"dcn={rec['hier_int8']['predicted_bytes_by_tier']['dcn']}B "
              f"vs measured "
              f"{(rec['hier_int8']['measured_bytes_by_tier'] or {}).get('dcn')}"
              f" (delta {rec['hier_int8']['delta_dcn']})")
    except Exception as e:  # noqa: BLE001 — evidence must not fail bench
        _progress_record("static_cost", error=str(e)[:160])


def _bench_hierarchy_sweep(hvd):
    """Hierarchical dispatch tier sweep (`HVD_BENCH_MODEL=hierarchy_sweep`):
    the SAME payload ladder through the eager allreduce under a forced
    slice hierarchy at flat / hierarchical / hierarchical+int8-cross
    strategy, reporting per-leg dispatch time and the PER-TIER
    `wire_bytes_total{tier}` deltas — the provable off-chip evidence that
    the decomposition divides DCN bytes by the slice width and the
    quantized cross leg shrinks them ~4x further
    (docs/performance.md "Hierarchical dispatch tier"). Every
    (payload, strategy) cell lands as a labeled `hierarchy_sweep` record
    on the HVD_BENCH_PROGRESS_FILE channel; the final BENCH record
    carries the hier-int8-vs-flat DCN byte ratio on the largest rung.
    Forces HOROVOD_MESH_SLICES=2 when the live topology has no slice
    hierarchy (the CPU tier's virtual hierarchy)."""
    from horovod_tpu.metrics import instruments as ins
    from horovod_tpu.ops import collective_ops as C, wire

    n = hvd.size()
    slices, _ = C._live_slices(n)
    if slices <= 1:
        os.environ["HOROVOD_MESH_SLICES"] = "2"  # hvdlint: disable=HVL003 -- bench-local virtual hierarchy for its own process; never exported to workers
        ins.reset_tier_split()
        slices, _ = C._live_slices(n)
    if slices <= 1:
        _emit_failure("hierarchy_sweep_dcn_bytes_ratio",
                      "hier-int8/flat DCN bytes ratio",
                      f"no slice hierarchy possible at world={n}")
        return 1
    iters = int(os.environ.get("HVD_BENCH_ITERS", "10"))
    ladder = [n * 1024, 128 * 1024, 1024 * 1024]
    rng = np.random.default_rng(0)

    def tier_bytes():
        out = {"ici": 0.0, "dcn": 0.0}
        snap = ins.get_registry().snapshot()
        for s in snap.get("wire_bytes_total", {}).get("series", ()):
            t = s["labels"].get("tier")
            if t in out:
                out[t] += s["value"]
        return out

    legs = (("flat", "flat", ""),
            ("hier", "hier", ""),
            ("hier_int8", "hier_qcross", "int8"))
    results = {}
    ratio_largest = 0.0
    for elems in ladder:
        x = jnp.asarray(rng.standard_normal((n, elems)), jnp.float32)
        payload_mb = x.nbytes / 2**20
        for leg, strategy, cross in legs:
            hvd.set_dispatch_strategy(strategy)
            hvd.set_wire_dtype(cross, tier="dcn")
            try:
                jax.block_until_ready(
                    hvd.allreduce(x, op=hvd.Sum))       # warm/compile
                b0 = tier_bytes()
                t0 = time.perf_counter()
                for _ in range(iters):
                    out = hvd.allreduce(x, op=hvd.Sum)
                jax.block_until_ready(out)
                dt = (time.perf_counter() - t0) / iters
                b1 = tier_bytes()
            finally:
                hvd.set_dispatch_strategy("")
                hvd.set_wire_dtype("", tier="dcn")
            delta = {t: (b1[t] - b0[t]) / max(iters, 1)
                     for t in ("ici", "dcn")}
            rec = {"payload_mb": round(payload_mb, 2), "strategy": leg,
                   "num_slices": slices,
                   "us_per_op": round(dt * 1e6, 1),
                   "ici_bytes_per_op": delta["ici"],
                   "dcn_bytes_per_op": delta["dcn"]}
            results[(elems, leg)] = {**rec, "tiers": delta}
            _progress_record("hierarchy_sweep", **rec)
            _mark(f"hierarchy_sweep {payload_mb:.1f}MB {leg}: "
                  f"{dt * 1e6:.0f}us/op, "
                  f"dcn {delta['dcn'] / 2**20:.3f} MB/op, "
                  f"ici {delta['ici'] / 2**20:.3f} MB/op")
        flat_dcn = results[(elems, "flat")]["tiers"]["dcn"]
        hier_dcn = results[(elems, "hier_int8")]["tiers"]["dcn"]
        if flat_dcn:
            ratio_largest = hier_dcn / flat_dcn
    largest = ladder[-1]
    _hierarchy_static_cost(hvd, largest, n, slices, {
        leg: results[(largest, leg)]["tiers"]
        for leg, _, _ in legs})
    wire.reset_error_feedback()
    _emit("hierarchy_sweep_dcn_bytes_ratio", round(ratio_largest, 4),
          "hier-int8/flat DCN bytes-on-wire ratio (largest rung; the "
          "decomposition holds DCN at flat-ring parity and the int8 "
          "cross leg takes it ~4x below)", 0.0)


def _moe_static_cost(hvd, shape, n, slices, measured):
    """The hvdcost ride-along for the MoE sweep: price the largest rung's
    expert-dispatch alltoall flat AND hierarchically (counterfactual
    pricing — use_registry=False so the sweep's own strategy/cross pins
    don't leak in) and record the per-tier prediction next to the
    measured `wire_bytes_total{tier}` deltas. The hierarchical legs must
    land at delta 0: the static model and _HierAlltoallPlan book the
    same wire.hierarchical_a2a_bytes integers."""
    try:
        from horovod_tpu.analysis import cost as an_cost
        from horovod_tpu.analysis.program import check_program
        from horovod_tpu.common.config import Config

        x = np.zeros((n,) + shape, np.float32)

        def step(x):
            return hvd.alltoall(x)

        rec = {"payload_mb": round(x.nbytes / 2**20, 2), "world": n,
               "num_slices": slices}
        legs = (("flat", Config()),
                ("hier", Config(hierarchical_alltoall=True)),
                ("hier_int8", Config(hierarchical_alltoall=True,
                                     alltoall_cross_dtype="int8")))
        for leg, cfg in legs:
            rep = check_program(step, (x,), world_size=n, config=cfg)
            cr = an_cost.cost_report(rep, config=cfg, num_slices=slices,
                                     use_registry=False)
            got = measured.get(leg)
            predicted = dict(cr.runtime_bytes_by_tier)
            rec[leg] = {
                "predicted_bytes_by_tier": predicted,
                "measured_bytes_by_tier": got,
                "delta_dcn": (got["dcn"] - predicted["dcn"])
                if got else None,
                "delta_ici": (got["ici"] - predicted["ici"])
                if got else None,
            }
        _progress_record("static_cost", static_cost=rec)
        _mark(f"static_cost moe: hier_int8 predicted "
              f"dcn={rec['hier_int8']['predicted_bytes_by_tier']['dcn']}B "
              f"vs measured "
              f"{(rec['hier_int8']['measured_bytes_by_tier'] or {}).get('dcn')}"
              f" (delta {rec['hier_int8']['delta_dcn']})")
    except Exception as e:  # noqa: BLE001 — evidence must not fail bench
        _progress_record("static_cost", error=str(e)[:160])


def _bench_moe_sweep(hvd):
    """Hierarchical expert-dispatch sweep (`HVD_BENCH_MODEL=moe_sweep`):
    the MoE dispatch alltoall — per-rank (tokens, hidden) expert slots,
    the shape parallel/moe.py exchanges — over a token/expert ladder at
    flat / hierarchical / hierarchical+int8-cross strategy under a
    forced 2-slice hierarchy, reporting per-leg dispatch time and the
    PER-TIER `wire_bytes_total{tier}` deltas. The provable evidence
    (docs/performance.md "Hierarchical expert dispatch"): the exact
    decomposition's DCN bytes equal the flat exchange's TOTAL divided by
    the slice width, and the block-scaled int8 cross leg takes them ~4x
    below that. Every (ladder, strategy) cell lands as a labeled
    `moe_sweep` record on HVD_BENCH_PROGRESS_FILE, plus a `static_cost`
    cross-check record (delta 0 on the hierarchical legs); the final
    BENCH record carries the int8-cross-vs-exact-hier DCN ratio on the
    largest rung."""
    from horovod_tpu.metrics import instruments as ins
    from horovod_tpu.ops import collective_ops as C, wire

    n = hvd.size()
    slices, _ = C._live_slices(n)
    if slices <= 1:
        os.environ["HOROVOD_MESH_SLICES"] = "2"  # hvdlint: disable=HVL003 -- bench-local virtual hierarchy for its own process; never exported to workers
        ins.reset_tier_split()
        C.clear_program_caches()
        slices, _ = C._live_slices(n)
    if slices <= 1:
        _emit_failure("moe_sweep_dcn_bytes_ratio",
                      "int8-cross/exact-hier DCN bytes ratio",
                      f"no slice hierarchy possible at world={n}")
        return 1
    iters = int(os.environ.get("HVD_BENCH_ITERS", "10"))
    # Token/expert ladder: capacity rows per (expert, peer) at a fixed
    # hidden size — per-rank payload (n*capacity, hidden), the dispatch
    # slots parallel/moe.py reshapes into (experts, capacity, hidden).
    hidden = 64
    ladder = [16, 128, 512]            # capacity rungs
    rng = np.random.default_rng(0)

    def tier_bytes():
        out = {"ici": 0.0, "dcn": 0.0}
        snap = ins.get_registry().snapshot()
        for s in snap.get("wire_bytes_total", {}).get("series", ()):
            t = s["labels"].get("tier")
            if t in out:
                out[t] += s["value"]
        return out

    legs = (("flat", "flat", ""),
            ("hier", "hier", ""),
            ("hier_int8", "hier_qcross", "int8"))
    results = {}
    ratio_largest = 0.0
    parity_largest = None
    for cap in ladder:
        x = jnp.asarray(
            rng.standard_normal((n, n * cap, hidden)), jnp.float32)
        payload_mb = x.nbytes / 2**20
        for leg, strategy, cross in legs:
            hvd.set_alltoall_strategy(strategy)
            hvd.set_alltoall_cross_dtype(cross)
            try:
                jax.block_until_ready(hvd.alltoall(x))   # warm/compile
                b0 = tier_bytes()
                t0 = time.perf_counter()
                for _ in range(iters):
                    out = hvd.alltoall(x)
                jax.block_until_ready(out)
                dt = (time.perf_counter() - t0) / iters
                b1 = tier_bytes()
            finally:
                hvd.set_alltoall_strategy("")
                hvd.set_alltoall_cross_dtype("")
            delta = {t: (b1[t] - b0[t]) / max(iters, 1)
                     for t in ("ici", "dcn")}
            rec = {"capacity": cap, "hidden": hidden,
                   "payload_mb": round(payload_mb, 2), "strategy": leg,
                   "num_slices": slices,
                   "us_per_op": round(dt * 1e6, 1),
                   "ici_bytes_per_op": delta["ici"],
                   "dcn_bytes_per_op": delta["dcn"]}
            results[(cap, leg)] = {**rec, "tiers": delta}
            _progress_record("moe_sweep", **rec)
            _mark(f"moe_sweep cap={cap} {leg}: {dt * 1e6:.0f}us/op, "
                  f"dcn {delta['dcn'] / 2**20:.3f} MB/op, "
                  f"ici {delta['ici'] / 2**20:.3f} MB/op")
        flat = results[(cap, "flat")]["tiers"]
        hier_dcn = results[(cap, "hier")]["tiers"]["dcn"]
        int8_dcn = results[(cap, "hier_int8")]["tiers"]["dcn"]
        # The acceptance identities: exact-hier DCN == flat TOTAL / S
        # (the cross leg's (S-1)/S split of the undivided exchange),
        # int8 cross well below that.
        parity_largest = hier_dcn - (flat["ici"] + flat["dcn"]) / slices
        if hier_dcn:
            ratio_largest = int8_dcn / hier_dcn
    largest = ladder[-1]
    _progress_record(
        "moe_sweep_summary", capacity=largest,
        dcn_parity_delta=parity_largest,
        int8_vs_hier_dcn_ratio=round(ratio_largest, 4))
    _moe_static_cost(hvd, (n * largest, hidden), n, slices, {
        leg: results[(largest, leg)]["tiers"]
        for leg, _, _ in legs})
    wire.clear_strategy_registry()
    wire.clear_wire_registry()
    wire.reset_error_feedback()
    _emit("moe_sweep_dcn_bytes_ratio", round(ratio_largest, 4),
          "int8-cross/exact-hier DCN bytes-on-wire ratio (largest rung; "
          "exact hierarchical dispatch holds DCN at flat-total/slices "
          "and the block-scaled int8 cross leg takes it ~4x below)", 0.0)


def _compression():
    """HVD_BENCH_COMPRESSION=none|bf16|fp16|int8|powersgd[:rank] — wire
    compression A/B for the training benches. On the single bench chip
    collectives are degenerate, so this measures each scheme's compute
    OVERHEAD (quantize/dequantize, low-rank factor math); the wire savings
    need a multi-chip run."""
    import horovod_tpu as hvd

    sel = os.environ.get("HVD_BENCH_COMPRESSION", "none")
    if sel == "powersgd" or sel.startswith("powersgd:"):
        rank = int(sel.split(":", 1)[1]) if ":" in sel else 4
        return hvd.Compression.powersgd(rank=rank)
    if sel in ("none", "bf16", "fp16", "int8"):
        return getattr(hvd.Compression, sel)
    raise ValueError(f"unknown HVD_BENCH_COMPRESSION={sel!r}")


def _bench_spec(hvd):
    """Speculative-decoding serving bench: GPT-2-small target decoding
    with KV-cached speculation (models/speculative.py). The draft is the
    TARGET itself (perfect draft, 100% acceptance): every block does the
    same forward work as gamma+1 plain cached steps, so the ratio vs the
    plain cached generate() baseline (stderr) measures the MACHINERY
    OVERHEAD — 1.0x means chunk-verify + cursor-rewind are free, and a
    real draft at cost c*target with acceptance alpha then delivers its
    textbook speedup undiminished. Reports generated tokens/sec/chip."""
    from horovod_tpu.models import GPT, GPTConfig, generate, \
        speculative_generate

    # SINGLE-CHIP serving bench: the decode path is not mesh-sharded, so
    # the batch is NOT scaled by world size and the metric is plain
    # tokens/sec on the serving chip (unlike the training benches).
    if hvd.size() > 1:
        _mark(f"note: spec bench is single-chip; {hvd.size() - 1} other "
              f"chip(s) idle")
    gen_len = int(os.environ.get("HVD_BENCH_GENLEN", "128"))
    gamma = int(os.environ.get("HVD_BENCH_SPEC_GAMMA", "4"))
    batch = int(os.environ.get("HVD_BENCH_BATCH", "8"))
    plen = max(1, min(32, gen_len // 2))   # prompt must fit small GENLENs
    # HVD_BENCH_KV_INT8=1: quantized decode cache — halves the per-step
    # cache bandwidth (the decode bottleneck); A/B against the default.
    kv_int8 = os.environ.get("HVD_BENCH_KV_INT8", "0") == "1"
    cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                    num_heads=12, intermediate_size=3072,
                    max_position_embeddings=gen_len + gamma + 1,
                    dtype=jnp.bfloat16, tp_axis=None, ep_axis=None,
                    kv_cache_int8=kv_int8)
    model = GPT(cfg)
    prompt = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, plen)), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), prompt)["params"]
    _mark("spec init done")

    def spec():
        return speculative_generate(model, params, model, params, prompt,
                                    max_len=gen_len, gamma=gamma,
                                    use_cache=True)

    out = spec()
    np.asarray(out)                       # sync: compile + warmup
    _mark("spec warmup done")
    iters = int(os.environ.get("HVD_BENCH_ITERS", "5"))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = spec()
    np.asarray(out)
    dt = time.perf_counter() - t0
    _mark(f"{iters} speculative decodes in {dt:.2f}s")
    toks = (gen_len - plen) * batch * iters
    # baseline: plain cached decode, same shapes (stderr only)
    base = generate(model, params, prompt, max_len=gen_len, use_cache=True)
    np.asarray(base)
    t0 = time.perf_counter()
    for _ in range(iters):
        base = generate(model, params, prompt, max_len=gen_len,
                        use_cache=True)
    np.asarray(base)
    dt_base = time.perf_counter() - t0
    _mark(f"baseline cached generate: "
          f"{toks / dt_base:.1f} tokens/sec/chip; self-draft ratio "
          f"{dt_base / dt:.2f}x at gamma={gamma} (1.0 = the speculation "
          f"machinery is overhead-free)")
    _emit("gpt2_speculative_tokens_per_sec_per_chip",
          round(toks / dt, 1), "tokens/sec/chip", 0.0)


def _bench_serving_sweep(hvd):
    """Continuous-batching serving bench (`HVD_BENCH_MODEL=serving_sweep`):
    a request-rate ladder through the serving engine — requests arrive
    paced at each rung's rate, the engine packs them into its fixed-slot
    decode batch, and every cell reports p50/p99 time-to-first-token,
    p50/p99 per-token latency, tokens/sec and peak queue depth as a
    labeled `serving_sweep` record on the HVD_BENCH_PROGRESS_FILE
    channel, followed by a
    `serving_trace` record per rung: mean queue/prefill/decode/stream
    fractions + coverage from each request's span tree and the SLO
    burn rates over the rung (bench-local HVD_BENCH_SLO_TTFT_MS
    objective when no HOROVOD_SLO_* is declared). The final BENCH
    record is the peak tokens/sec across rungs. Single-chip like the spec bench:
    the decode path is not mesh-sharded. Knobs: HVD_BENCH_SERVING_RATES
    (req/s ladder), HVD_BENCH_SERVING_REQUESTS (per rung),
    HVD_BENCH_SERVING_SLOTS, HVD_BENCH_GENLEN, HVD_BENCH_SERVING_GPT2=1
    for the full GPT-2-small (default: tiny config — the CPU tier
    measures the engine, not the matmuls)."""
    from horovod_tpu.models import GPT, GPTConfig
    from horovod_tpu.serving import ServingEngine

    if hvd.size() > 1:
        _mark(f"note: serving bench is single-chip; {hvd.size() - 1} "
              f"other chip(s) idle")
    gen_len = int(os.environ.get("HVD_BENCH_GENLEN", "32"))
    slots = int(os.environ.get("HVD_BENCH_SERVING_SLOTS", "4"))
    n_req = int(os.environ.get("HVD_BENCH_SERVING_REQUESTS", "24"))
    rates = [float(r) for r in os.environ.get(
        "HVD_BENCH_SERVING_RATES", "4,16,64").split(",")]
    plen = max(1, min(8, gen_len // 4))
    max_len = plen + gen_len + 1
    if os.environ.get("HVD_BENCH_SERVING_GPT2", "0") == "1":
        cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_heads=12, intermediate_size=3072,
                        max_position_embeddings=max_len,
                        dtype=jnp.bfloat16, tp_axis=None, ep_axis=None)
    else:
        cfg = GPTConfig.tiny(tp_axis=None, ep_axis=None,
                             max_position_embeddings=max_len)
    model = GPT(cfg)
    rng = np.random.default_rng(0)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0),
        jnp.zeros((1, plen), jnp.int32))["params"]
    _mark("serving init done")
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, plen)]
               for _ in range(n_req)]

    # Per-request trace summaries + SLO burn (ISSUE 16): declare a
    # bench-local SLO when none is configured so every rung's record
    # carries a burn-rate column (HVD_BENCH_SLO_TTFT_MS / _TPS override).
    import types

    from horovod_tpu import trace as _trace
    from horovod_tpu.telemetry import slo as _slo
    from horovod_tpu.trace import analyze as _trace_analyze
    if not _slo._get().configured():
        _slo.configure(types.SimpleNamespace(
            slo_ttft_p99_ms=float(os.environ.get(
                "HVD_BENCH_SLO_TTFT_MS", "250")),
            slo_tps=float(os.environ.get("HVD_BENCH_SLO_TPS", "0")),
            slo_window_s=300.0))

    peak_tps = 0.0
    for rate in rates:
        engine = ServingEngine(model, params, num_slots=slots,
                               max_len=max_len, mark_steps=False)
        # Warm the three compiled programs outside the timed window.
        w = engine.submit(prompts[0], max_new=2)
        engine.run_until_idle()
        w.result(0)
        t0 = time.perf_counter()
        reqs, nxt, peak_q = [], 0, 0
        while len(reqs) < n_req or not engine.idle():
            now = time.perf_counter() - t0
            while nxt < n_req and now >= nxt / rate:
                reqs.append(engine.submit(prompts[nxt], max_new=gen_len))
                nxt += 1
            peak_q = max(peak_q, engine.queue_depth())
            if not engine.step() and nxt < n_req:
                time.sleep(min(0.001, max(0.0, nxt / rate - now)))
        elapsed = time.perf_counter() - t0
        ttft = np.asarray([r.t_first - r.t_submit for r in reqs])
        tok_lat = np.asarray([
            (r.t_done - r.t_first) / max(len(r.committed) - 1, 1)
            for r in reqs])
        toks = sum(len(r.committed) for r in reqs)
        tps = toks / elapsed
        peak_tps = max(peak_tps, tps)
        cell = {
            "rate_rps": rate, "requests": n_req, "slots": slots,
            "gen_len": gen_len,
            "ttft_p50_ms": round(float(np.percentile(ttft, 50)) * 1e3, 2),
            "ttft_p99_ms": round(float(np.percentile(ttft, 99)) * 1e3, 2),
            "tok_p50_ms": round(float(np.percentile(tok_lat, 50)) * 1e3,
                                3),
            "tok_p99_ms": round(float(np.percentile(tok_lat, 99)) * 1e3,
                                3),
            "tokens_per_sec": round(tps, 1),
            "peak_queue_depth": peak_q,
        }
        _progress_record("serving_sweep", **cell)
        # Where the rung's latency went: per-request phase fractions
        # (queue/prefill/decode/stream of each root duration) from the
        # live span store, plus the window's burn rates — the same
        # summary `python -m horovod_tpu.trace.analyze` computes from
        # dumped shards, emitted on the progress channel per rung.
        summaries = [s for s in (_trace.get(r.tid) for r in reqs)
                     if s is not None]
        summaries = [_trace_analyze.summarize(s) for s in summaries]
        phase_mean = {
            n: round(float(np.mean([s["fractions"][n]
                                    for s in summaries])), 4)
            for n in _trace_analyze.PHASES} if summaries else {}
        burn = _slo.burn_rates()
        _progress_record(
            "serving_trace", rate_rps=rate,
            requests_traced=len(summaries),
            mean_fractions=phase_mean,
            mean_coverage=round(float(np.mean(
                [s["coverage"] for s in summaries])), 4)
            if summaries else 0.0,
            slo_burn=burn,
            per_request=summaries[:4])
        _mark(f"serving_sweep {rate:g} req/s: ttft p50/p99 "
              f"{cell['ttft_p50_ms']}/{cell['ttft_p99_ms']}ms, "
              f"tok p50/p99 {cell['tok_p50_ms']}/{cell['tok_p99_ms']}ms, "
              f"{tps:.1f} tok/s, peak queue {peak_q}, "
              f"burn {burn or '{}'}")
    _emit("serving_sweep_peak_tokens_per_sec", round(peak_tps, 1),
          "tokens/sec/chip (continuous-batching engine, peak across the "
          "request-rate ladder)", 0.0)


def _bench_control_sweep(hvd):
    """Control-plane sweep (`HVD_BENCH_MODEL=control_sweep`): negotiation
    rounds / blocking gets / payload bytes per round across a
    world x slices ladder, flat vs hierarchical, measured by driving the
    REAL exchange implementations at virtual world sizes (one thread per
    simulated rank over an in-memory KV —
    ``common/control_plane.simulate_exchange``, the same harness the
    n=128-512 dryrun guard in tests/test_multiproc.py uses). Every
    (world, slices, strategy) cell lands as a labeled `control_sweep`
    record on the HVD_BENCH_PROGRESS_FILE channel; the final BENCH
    record carries the hier-vs-flat worst-rank gets ratio at the largest
    world — the host-side fan-out collapse the hierarchy buys."""
    from horovod_tpu.common import control_plane as cp

    rounds = max(int(os.environ.get("HVD_BENCH_ITERS", "3")), 1)
    ladder = [(8, 2), (32, 4), (128, 8), (512, 16)]
    ratio_largest = 1.0
    for world, slices in ladder:
        cells = {}
        for strategy, k in (("flat", 0), ("hier", slices)):
            t0 = time.perf_counter()
            r = cp.simulate_exchange(world, k, rounds=rounds,
                                     strategy=strategy)
            wall = time.perf_counter() - t0
            worst = max(c["gets"] for c in r["per_proc"]) / rounds
            cell = {
                "world": world, "slices": r["num_slices"],
                "strategy": r["strategy"], "rounds": rounds,
                "identical": r["identical"],
                "gets_total_per_round": r["gets_total"] / rounds,
                "worst_rank_gets_per_round": worst,
                "member_gets_per_round": r["member_gets_per_round"],
                "leader_gets_per_round": r["leader_gets_per_round"],
                "payload_bytes_per_round": r["payload_bytes"] / rounds,
                "wall_s": round(wall, 3),
            }
            cells[r["strategy"]] = cell
            _progress_record("control_sweep", **cell)
            _mark(f"control_sweep w={world} s={slices} "
                  f"{r['strategy']}: worst-rank gets/round {worst:.0f}, "
                  f"member {cell['member_gets_per_round']:.0f}")
        if "hier" in cells and "flat" in cells:
            ratio_largest = cells["hier"]["worst_rank_gets_per_round"] \
                / max(cells["flat"]["worst_rank_gets_per_round"], 1.0)
    _progress_record("control_sweep_summary",
                     hier_vs_flat_worst_rank_gets_ratio=round(
                         ratio_largest, 4))
    _emit("control_sweep_worst_rank_gets_ratio", round(ratio_largest, 4),
          "hier/flat worst-rank negotiation gets ratio", 0.0)
    return 0


def _bench_twin_sweep(hvd):
    """Scale-twin sweep (`HVD_BENCH_MODEL=twin_sweep`): the control_sweep
    ladder continued past the thread-feasible worlds through the hvdsim
    event twin (``horovod_tpu/sim`` — virtual ranks over a deterministic
    event heap, the same exchange math) up to n=65536. Hier cells are
    event-simulated at every rung; flat past ``sim.FLAT_WORLD_CAP``
    would be O(world^2) events, so those cells are priced analytically
    from ``control_plane.exchange_plan`` + the twin latency model
    (labeled ``priced="analytic"``). Every (world, slices, strategy)
    cell lands as a `twin_sweep` record on the HVD_BENCH_PROGRESS_FILE
    channel; the final BENCH record carries the hier-vs-flat worst-rank
    gets ratio at n=65536 — the fan-out collapse, now measured two
    orders of magnitude past the thread dryrun."""
    from horovod_tpu.common import control_plane as cp
    from horovod_tpu.sim import FLAT_WORLD_CAP, LatencyModel
    from horovod_tpu.sim.control import twin_exchange

    rounds = max(int(os.environ.get("HVD_BENCH_ITERS", "2")), 1)
    latency = LatencyModel.from_env()
    ladder = [(512, 16), (4096, 64), (16384, 64), (65536, 256)]
    ratio_largest = 1.0
    for world, slices in ladder:
        cells = {}
        for strategy, k in (("flat", 0), ("hier", slices)):
            t0 = time.perf_counter()
            if strategy == "flat" and world > FLAT_WORLD_CAP:
                plan = cp.exchange_plan(world, 1)
                worst = float(plan["leader_gets"])
                cell = {
                    "world": world, "slices": 1, "strategy": "flat",
                    "rounds": rounds, "priced": "analytic",
                    "identical": True,
                    "gets_total_per_round": plan["round_gets_total"],
                    "worst_rank_gets_per_round": worst,
                    "member_gets_per_round": float(plan["member_gets"]),
                    "leader_gets_per_round": worst,
                    # serial blocking chain of the worst rank, priced by
                    # the same per-RPC latency model the event twin uses
                    "virtual_s": round(worst * latency.seconds(False), 6),
                }
            else:
                r = twin_exchange(world, k, rounds=rounds,
                                  strategy=strategy, latency=latency)
                worst = max(c["gets"] for c in r["per_proc"]) / rounds
                cell = {
                    "world": world, "slices": r["num_slices"],
                    "strategy": r["strategy"], "rounds": rounds,
                    "priced": "event", "identical": r["identical"],
                    "gets_total_per_round": r["gets_total"] / rounds,
                    "worst_rank_gets_per_round": worst,
                    "member_gets_per_round": r["member_gets_per_round"],
                    "leader_gets_per_round": r["leader_gets_per_round"],
                    "payload_bytes_per_round":
                        r["payload_bytes"] / rounds,
                    "events": r["events"],
                    "virtual_s": round(r["virtual_s"] / rounds, 6),
                }
            cell["wall_s"] = round(time.perf_counter() - t0, 3)
            cells[cell["strategy"]] = cell
            _progress_record("twin_sweep", **cell)
            _mark(f"twin_sweep w={world} s={slices} {cell['strategy']} "
                  f"[{cell['priced']}]: worst-rank gets/round "
                  f"{cell['worst_rank_gets_per_round']:.0f}, "
                  f"virtual {cell['virtual_s']*1e3:.2f} ms, "
                  f"wall {cell['wall_s']:.2f} s")
        if "hier" in cells and "flat" in cells:
            ratio_largest = cells["hier"]["worst_rank_gets_per_round"] \
                / max(cells["flat"]["worst_rank_gets_per_round"], 1.0)
    _progress_record("twin_sweep_summary",
                     hier_vs_flat_worst_rank_gets_ratio=round(
                         ratio_largest, 6))
    _emit("twin_sweep_worst_rank_gets_ratio", round(ratio_largest, 6),
          "hier/flat worst-rank negotiation gets ratio at n=65536 "
          "(event twin)", 0.0)
    return 0


def _bench_autopilot_sweep(hvd):
    """Autopilot convergence sweep (`HVD_BENCH_MODEL=autopilot_sweep`):
    start the runtime deliberately detuned (tiny fusion threshold, flat
    dispatch, full-precision cross wire), then let the
    horovod_tpu/autopilot controller drive its decision epochs over a
    fixed async-allreduce workload. Every epoch's decisions land as
    labeled `autopilot_sweep` records on the HVD_BENCH_PROGRESS_FILE
    channel (epoch, lever, outcome, knobs, score, per-tier DCN bytes),
    and the final BENCH record carries the converged-vs-detuned score
    ratio."""
    from horovod_tpu.common import basics
    from horovod_tpu.ops import fusion, wire
    from horovod_tpu.autopilot.controller import AutopilotController

    # A virtual slice hierarchy when the backend has none (the forced
    # layout resolves live — PR-12 seam), so the strategy/cross-wire
    # levers have something to steer on single-slice boxes too.
    forced_env = False
    if "HOROVOD_MESH_SLICES" not in os.environ:
        from horovod_tpu.common.topology import forced_slices
        topo = basics.topology()
        if not forced_slices() and topo.num_slices <= 1 \
                and hvd.size() % 2 == 0 and hvd.size() > 1:
            os.environ["HOROVOD_MESH_SLICES"] = "2"  # hvdlint: disable=HVL003 -- bench-local virtual hierarchy for its own process; never exported to workers
            forced_env = True

    cfg = basics.config()
    prev_cfg = (cfg.autotune_warmup_samples,
                cfg.autotune_bayes_opt_max_samples)
    cfg.autotune_warmup_samples = 0
    cfg.autotune_bayes_opt_max_samples = int(
        os.environ.get("HVD_BENCH_ITERS", "6"))
    rt = fusion.get_runtime()
    prev = (rt.threshold, rt._cycle_s, rt.strategy, rt.cross_wire,
            rt.wire_dtype, rt._overlap_mode, rt._overlap_pinned)
    rt.threshold = 64 * 1024
    rt.strategy = "flat"
    ctrl = AutopilotController(cfg)

    n = hvd.size()
    rng = np.random.default_rng(0)
    xs = [jnp.asarray(rng.standard_normal((n, 64 * 1024)), jnp.float32)
          for _ in range(6)]
    step = [0]

    def run_epoch():
        for _ in range(2):
            hvd.grouped_allreduce_async(
                xs, op=hvd.Average, name="autopilot_sweep").synchronize()
            step[0] += 1
            hvd.step_marker(step[0])

    first_score = None
    last_score = None
    max_epochs = 48
    try:
        for _ in range(max_epochs):
            run_epoch()
            for rec in ctrl.tick():
                row = {k: rec.get(k) for k in
                       ("epoch", "lever", "outcome", "threshold",
                        "cycle_ms", "categoricals", "score")}
                row["signal"] = rec.get("signal")
                _progress_record("autopilot_sweep", **row)
                if rec.get("score") is not None:
                    if first_score is None:
                        first_score = rec["score"]
                    last_score = rec["score"]
            if ctrl.frozen and ctrl._cross_trial is None:
                break
        _progress_record(
            "autopilot_sweep_summary", frozen=ctrl.frozen,
            epochs=ctrl.epoch, threshold=rt.threshold,
            strategy=rt.strategy, cross_wire=rt.cross_wire,
            decisions=len(ctrl.decisions()))
        _mark(f"autopilot_sweep: frozen={ctrl.frozen} after "
              f"{ctrl.epoch} epochs -> threshold={rt.threshold} "
              f"strategy={rt.strategy} cross={rt.cross_wire or 'exact'}")
    finally:
        ctrl.stop()
        (rt.threshold, rt._cycle_s, rt.strategy, rt.cross_wire,
         rt.wire_dtype, rt._overlap_mode, rt._overlap_pinned) = prev
        (cfg.autotune_warmup_samples,
         cfg.autotune_bayes_opt_max_samples) = prev_cfg
        if forced_env:
            os.environ.pop("HOROVOD_MESH_SLICES", None)
        wire.clear_strategy_registry()
        wire.clear_wire_registry()
        wire.reset_error_feedback()
        from horovod_tpu.metrics import instruments as _ins
        _ins.reset_tier_split()
    ratio = (last_score / first_score) if first_score else 0.0
    _emit("autopilot_sweep_score_ratio", round(ratio, 4),
          "converged/detuned autopilot score ratio (reduced bytes/sec, "
          "DCN-priced; >1 = the controller improved the config)", 0.0)
    return 0


def _bench_goodput_sweep(hvd):
    """Goodput-decomposition fidelity sweep: drive a fake-clock
    :class:`~horovod_tpu.goodput.ledger.GoodputLedger` through a KNOWN
    injected badput schedule (compile stall, straggler steps, checkpoint
    commits, an autopilot trial window, exposed cross-slice waits, a
    wedge, an elastic reset) and assert the measured decomposition
    recovers every injected quantity exactly — the virtual clock leaves
    no jitter to hide behind. Each schedule leg lands as a labeled
    ``goodput_sweep`` record on the HVD_BENCH_PROGRESS_FILE channel; the
    final BENCH record carries recovered/injected badput ratio (1.0 =
    perfect recovery) and the conservation error."""
    from horovod_tpu.goodput.ledger import (GoodputLedger,
                                            PRODUCTIVE as PRODUCTIVE_CAT)

    led = GoodputLedger()
    t = 0.0
    led.start(now=t)

    def step_rec(comm=0.1, cross=0.0):
        return {"attribution": {"host_dispatch": comm / 2,
                                "collective": comm / 2,
                                "cross_wait": cross}}

    def boundary(dt, step, rec):
        nonlocal t
        t += dt
        led.on_step_boundary(rec, step=step, now=t)

    injected = {"init_compile": 5.0, "straggler_wait": 2.0,
                "checkpoint_commit": 2.0, "autopilot_trial": 3.0,
                "cross_wait_comm": 0.6, "wedge_idle": 2.0,
                "rendezvous_recovery": 4.5}
    step = 0
    boundary(5.0, step, None)               # compile stall -> init_compile
    _progress_record("goodput_sweep", leg="init", injected_s=5.0)
    for _ in range(12):                     # clean baseline (builds the
        step += 1                           # rolling comm median)
        boundary(1.0, step, step_rec())
    for _ in range(4):                      # straggler: comm 0.5s over the
        step += 1                           # 0.1s median -> 0.5s excess/step
        boundary(1.0, step, step_rec(comm=0.6))
    _progress_record("goodput_sweep", leg="straggler", injected_s=2.0)
    led.note_commit(2.0)                    # checkpoint: consumed from the
    for _ in range(2):                      # next two 1s windows
        step += 1
        boundary(1.0, step, step_rec())
    _progress_record("goodput_sweep", leg="commit", injected_s=2.0)
    led.set_trial(True)                     # autopilot trial window
    for _ in range(3):
        step += 1
        boundary(1.0, step, step_rec())
    led.set_trial(False)
    _progress_record("goodput_sweep", leg="trial", injected_s=3.0)
    for _ in range(2):                      # exposed cross-slice wait
        step += 1
        boundary(1.0, step, step_rec(cross=0.3))
    _progress_record("goodput_sweep", leg="cross_wait", injected_s=0.6)
    led.note_wedge(now=t)                   # stall verdict, recovers
    t += 2.0                                # without a reset
    led.note_unwedged(now=t)
    _progress_record("goodput_sweep", leg="wedge", injected_s=2.0)
    t += 1.5                                # reset mid-window: the lost
    led.on_reset(now=t)                     # partial step is badput too
    t += 3.0                                # rendezvous + restore; the first
    step += 1                               # post-restore marker only OPENS
    led.on_step_boundary(None, step=step, now=t)  # a window (profile
    # ledger was reset) -> the whole gap books to rendezvous_recovery
    _progress_record("goodput_sweep", leg="reset",
                     injected_s=1.5 + 3.0)
    for _ in range(2):                      # post-recovery steps
        step += 1
        boundary(1.0, step, step_rec())

    snap = led.assert_conservation(now=t, tol=1e-9)
    cats = snap["categories"]
    worst = ""
    recovered = injected_total = 0.0
    for cat, want in injected.items():
        got = cats.get(cat, 0.0)
        injected_total += want
        recovered += got
        if abs(got - want) > 1e-6:
            worst = (f"{cat}: recovered {got:.6f}s of injected "
                     f"{want:.6f}s")
    expect_productive = 12.0 + 4 * 0.5 + 2 * 0.7 + 2.0
    if abs(cats[PRODUCTIVE_CAT] - expect_productive) > 1e-6:
        worst = worst or (f"productive_compute: {cats[PRODUCTIVE_CAT]:.6f}"
                          f"s vs expected {expect_productive:.6f}s")
    _progress_record(
        "goodput_sweep_summary", categories=cats,
        conservation_error=snap["conservation_error"],
        goodput_ratio=snap["goodput_ratio"], mismatch=worst or None)
    if worst:
        raise RuntimeError(f"goodput_sweep decomposition mismatch — "
                           f"{worst}")
    ratio = recovered / injected_total
    _mark(f"goodput_sweep: recovered {recovered:.2f}s of "
          f"{injected_total:.2f}s injected badput "
          f"(conservation error {snap['conservation_error']:.2e})")
    _emit("goodput_sweep_recovered_ratio", round(ratio, 6),
          "recovered/injected badput seconds (fake-clock schedule; "
          "1.0 = the decomposition names every injected fault)", 0.0)
    return 0


# Non-image benchmarks: selector -> (bench fn, metric name, unit). One
# registry so dispatch and failure records can never disagree.
_EXTRA_MODELS = {
    "bert": (_bench_bert, "bert_large_seqs_per_sec_per_chip",
             "sequences/sec/chip"),
    "gpt": (_bench_gpt, "gpt2_small_tokens_per_sec_per_chip",
            "tokens/sec/chip"),
    "vit": (_bench_vit, "vit_b16_images_per_sec_per_chip",
            "images/sec/chip"),
    "llama": (_bench_llama, "llama_400m_tokens_per_sec_per_chip",
              "tokens/sec/chip"),
    "t5": (_bench_t5, "t5_small_tokens_per_sec_per_chip",
           "tokens/sec/chip"),
    "spec": (_bench_spec, "gpt2_speculative_tokens_per_sec_per_chip",
             "tokens/sec/chip"),
    "wire_sweep": (_bench_wire_sweep, "wire_sweep_int8_bytes_ratio",
                   "int8/fp32 bytes-on-wire ratio"),
    "hierarchy_sweep": (_bench_hierarchy_sweep,
                        "hierarchy_sweep_dcn_bytes_ratio",
                        "hier-int8/flat DCN bytes ratio"),
    "moe_sweep": (_bench_moe_sweep, "moe_sweep_dcn_bytes_ratio",
                  "int8-cross/exact-hier DCN bytes ratio"),
    "serving_sweep": (_bench_serving_sweep,
                      "serving_sweep_peak_tokens_per_sec",
                      "tokens/sec/chip"),
    "control_sweep": (_bench_control_sweep,
                      "control_sweep_worst_rank_gets_ratio",
                      "hier/flat worst-rank negotiation gets ratio"),
    "autopilot_sweep": (_bench_autopilot_sweep,
                        "autopilot_sweep_score_ratio",
                        "converged/detuned autopilot score ratio"),
    "twin_sweep": (_bench_twin_sweep,
                   "twin_sweep_worst_rank_gets_ratio",
                   "hier/flat worst-rank negotiation gets ratio at "
                   "n=65536 (event twin)"),
    "goodput_sweep": (_bench_goodput_sweep,
                      "goodput_sweep_recovered_ratio",
                      "recovered/injected badput seconds"),
}


def main():
    import horovod_tpu as hvd

    metric, unit = _failure_metric()
    _arm_watchdog(float(os.environ.get("HVD_BENCH_WATCHDOG", "1500")),
                  metric, unit)
    hvd.init()
    device = _device()
    _mark(f"hvd.init done on {device}")
    if device["platform"] != "tpu" \
            and os.environ.get("HVD_BENCH_ALLOW_CPU", "0") != "1":
        # A number from the host CPU says nothing about the framework on
        # its device: fail, naming what was found.
        raise RuntimeError(
            f"no TPU: platform is {device['platform']!r} "
            f"({device['kind']} x{device['count']}); set "
            f"HVD_BENCH_ALLOW_CPU=1 to run the CPU-tier legs")
    model_sel = os.environ.get("HVD_BENCH_MODEL", "resnet50")
    if model_sel in _EXTRA_MODELS:
        return _EXTRA_MODELS[model_sel][0](hvd)
    if model_sel not in _IMAGE_MODELS:
        raise ValueError(
            f"unknown HVD_BENCH_MODEL={model_sel!r}; choose from "
            f"{sorted(_IMAGE_MODELS) + sorted(_EXTRA_MODELS)}")
    return _bench_image(hvd, model_sel)


def _failure_metric():
    """Failure-record metric name for the SELECTED benchmark, so a BERT/GPT
    failure never reads as a resnet50 regression."""
    sel = os.environ.get("HVD_BENCH_MODEL", "resnet50")
    if sel in _EXTRA_MODELS:
        return _EXTRA_MODELS[sel][1], _EXTRA_MODELS[sel][2]
    name = sel if sel in _IMAGE_MODELS else "resnet50"
    return f"{name}_images_per_sec_per_chip", "images/sec/chip"


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:  # noqa: BLE001
        # Emit a parseable failure record (cancel the watchdog FIRST: its
        # boom() racing this print could interleave two JSON lines or
        # truncate this one).
        _watchdog_cancel()
        metric, unit = _failure_metric()
        _emit_failure(
            metric, unit,
            (str(e).splitlines() or ["?"])[0][:200] or repr(e)[:200])
        sys.exit(1)
