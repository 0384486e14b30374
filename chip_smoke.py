#!/usr/bin/env python
"""Chip smoke: the training main path, once, on the accelerator.

    python chip_smoke.py        # every chip this process can see

Trains GPT-2 small (vocab 50304, hidden 768, 12 layers, 12 heads, seq 1024,
bf16, Pallas flash attention, 8 sequences per chip) for a few steps through
the entry points a user calls: ``hvd.init`` -> ``hvd.broadcast_parameters``
-> ``DistributedOptimizer(optax.adamw)`` -> ``parallel.make_train_step`` ->
``hvd.shutdown``. Weights are random, from a seed; nothing is read from disk
or the network. It is not a benchmark: the times it prints are information.

Every phase raises on failure and nothing catches it, so any failure, and
any platform other than ``tpu``, ends the process non-zero before a result
line is printed. On success the last line of stdout is exactly
``{"ok": true, "device": {"platform", "kind", "count"}}``; the line before
it is the run's summary (losses, compile seconds, cache hits, no claim).
"""

import importlib.metadata
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import native
from horovod_tpu.models.gpt import GPT, GPTConfig
from horovod_tpu.optim import DistributedOptimizer
from horovod_tpu.parallel import TrainState, make_train_step, shard_batch

SEQ = 1024
STEPS = 6
# The two Mosaic kernels a flash-attention train step must contain (names
# given to pl.pallas_call in ops/pallas/flash_attention.py): the forward and
# the one backward kernel, which a call of 1024 takes (backward_path).
FLASH_KERNELS = ("hvd_flash_fwd", "hvd_flash_bwd_dqkv")


def say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, what):
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def cache_events():
    """(request, hit) totals of ``compile_cache_events_total``."""
    fam = hvd.metrics_snapshot().get("compile_cache_events_total")
    ev = {s["labels"]["event"]: int(s["value"])
          for s in (fam or {"series": []})["series"]}
    return ev.get("request", 0), ev.get("hit", 0)


def eager_allreduce(n):
    """Sum and Average over the rank-major stacked layout against numpy."""
    x = np.arange(n * 257, dtype=np.float32).reshape(n, 257) / 7.0
    total = np.asarray(hvd.allreduce(jnp.asarray(x), op=hvd.Sum))
    mean = np.asarray(hvd.allreduce(jnp.asarray(x), op=hvd.Average))
    check(total.shape == x.shape and mean.shape == x.shape,
          f"allreduce shapes {total.shape}/{mean.shape} != {x.shape}")
    want = np.broadcast_to(x.sum(axis=0), x.shape)
    np.testing.assert_allclose(total, want, rtol=1e-6)
    np.testing.assert_allclose(mean, want / n, rtol=1e-6)
    say(f"eager allreduce Sum/Average over {n} rank(s): match numpy")


def build(n, mesh, per_chip, num_layers):
    cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=num_layers,
                    num_heads=12, intermediate_size=3072,
                    max_position_embeddings=SEQ, dtype=jnp.bfloat16,
                    tp_axis=None, ep_axis=None, use_flash=True, remat=False)
    model = GPT(cfg)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (per_chip * n, SEQ)).astype(np.int32)

    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch["ids"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1].astype(jnp.float32), batch["ids"][:, 1:]).mean()

    variables = jax.jit(model.init)(jax.random.PRNGKey(0), ids[:1])
    params = hvd.broadcast_parameters(variables["params"], root_rank=0)
    opt = DistributedOptimizer(optax.adamw(1e-4))
    # State replicated on every chip and the batch split over the mesh
    # before the first call: left on the default device, the batch is
    # re-scattered from chip 0 each step and the state's move to the mesh
    # after step 1 costs a second full compile.
    state = jax.device_put(TrainState.create(params, opt),
                           NamedSharding(mesh, P()))
    batch = shard_batch({"ids": ids}, mesh)
    shards = batch["ids"].addressable_shards
    check(len({s.device for s in shards}) == n
          and all(s.data.shape == (per_chip, SEQ) for s in shards),
          f"batch not split {per_chip}/chip over {n} chips: "
          f"{[(str(s.device), s.data.shape) for s in shards]}")
    step = make_train_step(loss_fn, opt, mesh, donate=True)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    say(f"model built: {n_params / 1e6:.1f}M params, global batch "
        f"{per_chip * n} x {SEQ}, {per_chip}/chip")
    return step, state, batch


def compile_step(step, state, batch, num_layers):
    req0, hit0 = cache_events()
    t0 = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    seconds = time.perf_counter() - t0
    req1, hit1 = cache_events()
    mosaic = [ln for ln in compiled.as_text().splitlines()
              if 'custom_call_target="tpu_custom_call"' in ln]
    found = {k: sum(f"/{k}/pallas_call" in ln for ln in mosaic)
             for k in FLASH_KERNELS}
    check(all(c == num_layers for c in found.values()),
          f"compiled step lacks the flash Mosaic kernels: {found} "
          f"(want {num_layers} each; {len(mosaic)} tpu_custom_call in all)")
    say(f"train step compiled in {seconds:.1f}s; compile cache "
        f"request={req1 - req0} hit={hit1 - hit0}; Mosaic calls {found}")
    return compiled, seconds, (req1 - req0, hit1 - hit0)


def train(compiled, state, batch):
    losses, times = [], []
    for i in range(STEPS):
        t0 = time.perf_counter()
        state, loss = compiled(state, batch)
        jax.block_until_ready((state, loss))
        times.append(time.perf_counter() - t0)
        losses.append(float(loss))
        say(f"step {i + 1}: loss {losses[-1]:.6f}  {times[-1] * 1e3:.1f} ms")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return state, losses, times


def check_replicated(state, n):
    """Every state leaf on all ``n`` chips, the copies bit-identical — what
    ``make_train_step`` relies on when it returns the state as replicated
    without a check."""
    leaves = jax.tree_util.tree_leaves(state)
    for leaf in leaves:
        shards = leaf.addressable_shards
        check(len({s.device for s in shards}) == n
              and all(s.data.shape == leaf.shape for s in shards),
              f"state leaf {leaf.shape} not resident whole on all {n} chips")
        first = np.asarray(shards[0].data)
        for s in shards[1:]:
            check(np.asarray(s.data).tobytes() == first.tobytes(),
                  f"state leaf {leaf.shape} differs on {s.device}")
    say(f"state: {len(leaves)} leaves on all {n} chip(s), bit-identical")


def main(per_chip=8, num_layers=12):
    hvd.init()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"device {device}; jax {jax.__version__}, jaxlib "
        f"{importlib.metadata.version('jaxlib')}, libtpu "
        f"{importlib.metadata.version('libtpu')}")
    say(f"compile cache dir: {jax.config.jax_compilation_cache_dir}")
    check(device["platform"] == "tpu",
          f"platform is {device['platform']!r} ({device['kind']}), "
          f"need 'tpu'")
    n = hvd.size()
    check(n == len(devices),
          f"topology size {n} != {len(devices)} chips seen")
    say("host runtime: " + ("native (libhvdtpu.so built)"
                            if native.native_built() else "pure Python"))

    eager_allreduce(n)
    mesh = hvd.global_process_set.mesh
    step, state, batch = build(n, mesh, per_chip, num_layers)
    compiled, compile_s, step_cache = compile_step(step, state, batch,
                                                   num_layers)
    state, losses, times = train(compiled, state, batch)
    check_replicated(state, n)
    say("peak device memory per chip, MiB (information): "
        f"{[d.memory_stats()['peak_bytes_in_use'] >> 20 for d in devices]}")
    req, hit = cache_events()
    say(f"compile_cache_events_total: request={req} hit={hit}")
    hvd.shutdown()
    say("summary " + json.dumps({
        "size": n, "per_chip_batch": per_chip, "seq": SEQ,
        "layers": num_layers, "losses": [round(x, 6) for x in losses],
        "step_compile_s": round(compile_s, 2),
        "step_cache": {"request": step_cache[0], "hit": step_cache[1]},
        "cache": {"dir": jax.config.jax_compilation_cache_dir,
                  "request": req, "hit": hit},
        "step_ms_info": [round(t * 1e3, 1) for t in times],
        "claim": None}))
    # The result line the chip check reads: these keys and no others.
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
