#!/usr/bin/env python3
"""A sparse cell's load while its real step trains: how many rows a token
sends to the experts one chip holds, in every sparse layer, every 8 steps.

    python3 benchmark/tools/load_probe.py --workload <cell> --stds 0.5,1.0
        --seeds 101,102 [--steps 48] [--norm post_attn_norm] [--rehearse]

For each embedding std (``assumed.embedding_std``) and seed: the program as
a benchmark run builds it, fed the cell's traffic for ``--steps`` steps.
Every 8 steps the model is applied to the step's batch with the output of
every module named ``--norm`` (the norm whose output a sparse layer's
router reads) captured, and each router's choice of ``k`` experts is taken
from it: the share of choices that fall on the experts held, times ``k``,
is the rows a token sends to them (``k x held / published`` expected, the
buffer's rows a token above it). A router that routes by what every
token's stream holds in common, and not by the token, swings that share to
0 or to ``k``. Prints one ``[probe]`` line a (std, seed). Not part of a
benchmark run.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench  # noqa: E402
from benchmark.harness import manifest as manifest_mod  # noqa: E402


def sparse_layers(params, inter, norm):
    """(path, router kernel, experts held, captured norm output) of every
    sparse layer: a subtree whose params hold ``moe/router`` and whose
    intermediates hold ``norm``'s output."""
    out = []

    def walk(p, i, path):
        if "moe" in p and "router" in p["moe"] and norm in i:
            out.append(("/".join(path), p["moe"]["router"]["kernel"],
                        p["moe"]["w_down"].shape[0], i[norm]["__call__"][0]))
        for key, sub in p.items():
            if isinstance(sub, dict) and isinstance(i.get(key), dict):
                walk(sub, i[key], path + [key])

    walk(params, inter, [])
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--stds", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--norm", default="post_attn_norm")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    _, workload, cfg = bench.load_cell(manifest_mod.load(ROOT),
                                       args.workload, args.rehearse)
    chips = workload["chips"]
    if args.rehearse:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={chips}")
    import jax
    import jax.numpy as jnp
    from horovod_tpu.parallel import shard_batch
    from benchmark.harness import program, reference, traffic, weights
    if (jax.devices()[0].platform == "tpu") == args.rehearse:
        raise SystemExit("needs a TPU, or --rehearse and none")
    hvd, mesh = program.start(chips)
    model, loss_fn = program.load_model_builder(cfg["model"])(cfg)
    k = cfg["num_experts_per_tok"]

    @jax.jit
    def loads(params, ids):
        _, st = model.apply({"params": params}, ids,
                            mutable=["intermediates"],
                            capture_intermediates=lambda m, n:
                            m.name == args.norm)
        got = {}
        for path, kernel, held, x in sparse_layers(
                params, st["intermediates"], args.norm):
            logits = x.reshape(-1, x.shape[-1]).astype(jnp.float32) @ kernel
            chosen = jax.lax.top_k(logits, k)[1] - first
            got[path] = k * jnp.mean((chosen >= 0) & (chosen < held))
            expected[path] = k * held / kernel.shape[-1]
        return got

    first = cfg["deployment"].get("first_expert_held", 0)
    expected = {}
    shapes = reference.param_shapes(cfg)
    compiled = None
    for std in (float(s) for s in args.stds.split(",")):
        c = dict(cfg, assumed=dict(cfg["assumed"], embedding_std=std))
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            step, state = program.build(hvd, mesh, c, loss_fn,
                                        weights.make_params(shapes, seed, c))
            batches = traffic.Batches(c, workload, seed)
            rows, layers = [], None
            for s in range(args.steps + 1):
                b = batches.next()
                if s % 8 == 0:
                    got = jax.device_get(loads(state.params,
                                               jnp.asarray(b["ids"])))
                    layers = sorted(got)
                    rows.append([s] + [round(float(got[n]), 4)
                                       for n in layers])
                if s == args.steps:
                    break
                if compiled is None:
                    compiled, secs = program.compile_step(
                        step, state, shard_batch(b, mesh))
                    print(f"[probe] compiled in {secs:.1f} s", flush=True)
                state, loss = program.feed(compiled, mesh, state, b)
                if s % 8 == 0:
                    rows[-1].append(round(float(loss), 4))
            del state, step
            flat = [v for r in rows for v in r[1:1 + len(layers)]]
            print("[probe] " + json.dumps(
                {"std": std, "seed": seed, "layers": layers, "rows": rows,
                 "min": min(flat), "max": max(flat),
                 "expected": expected[layers[0]],
                 "seconds": round(time.perf_counter() - t0, 1)}),
                flush=True)
    hvd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
