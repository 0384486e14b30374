"""The repo's kernels (flash attention, the grouped products, the state-space
scan) compiled for a described TPU v5e, without the chip.

The Pallas interpreter (tests/test_pallas.py) checks the kernels'
arithmetic; it cannot see what mosaic refuses: a slice not aligned to the
(8, 128) tiling, more scoped VMEM than a kernel may use. These tests lower
and compile forward and backward at the shapes the models call them with,
for a chip that is described and not attached. Nothing runs: they say
nothing about results or times.

The topology is described inside a fixture (never at import: one process
at a time may load the TPU's library, and every xdist worker imports every
test file), and all such tests live in this one file.
"""

import importlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no compiler here, or another process has it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def fa(monkeypatch):
    """The kernel module, steered onto its TPU branch (compiled kernels,
    fused backward): the process's backend is still the CPU."""
    mod = importlib.import_module("horovod_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(mod, "_interpret", lambda: False)
    monkeypatch.delenv("HVD_FLASH_BLOCK", raising=False)
    return mod


def _flash_kernels(hlo):
    """The flash kernels a compiled program holds, by their whole names
    (``hvd_flash_bwd_dq`` is a prefix of ``hvd_flash_bwd_dqkv``)."""
    return set(re.findall(r"hvd_flash_[a-z]+(?:_[a-z]+)*", hlo))


FUSED = {"hvd_flash_fwd", "hvd_flash_bwd_dqkv"}
PAIR = {"hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv"}


# (batch, lq, lk, heads, kv heads, head size, causal, dtype[, window])
_CALLS = {
    "gpt2_medium_1024_causal": (8, 1024, 1024, 16, 16, 64, True, "bfloat16"),
    "gpt2_1024_causal_float32": (2, 1024, 1024, 12, 12, 64, True, "float32"),
    "bert_large_128": (32, 128, 128, 16, 16, 64, False, "bfloat16"),
    "bert_large_512": (8, 512, 512, 16, 16, 64, False, "bfloat16"),
    "one_tile_1024_noncausal": (2, 1024, 1024, 16, 16, 64, False, "bfloat16"),
    "vit_196_padded_to_256": (8, 196, 196, 12, 12, 64, False, "bfloat16"),
    "short_96_causal": (4, 96, 96, 4, 4, 64, True, "bfloat16"),
    "causal_300_padded_to_384": (2, 300, 300, 8, 8, 64, True, "bfloat16"),
    "llama_gqa_2048_causal": (2, 2048, 2048, 16, 4, 128, True, "bfloat16"),
    "causal_8192_chunked": (1, 8192, 8192, 8, 8, 64, True, "bfloat16"),
    "noncausal_8192_chunked": (1, 8192, 8192, 8, 8, 64, False, "bfloat16"),
    "prefill_256_on_1024_keys": (2, 256, 1024, 8, 8, 64, True, "bfloat16"),
    # a ninth field is the sliding window
    "gqa_28_on_4_8192_window_4096": (2, 8192, 8192, 28, 4, 128, True,
                                     "bfloat16", 4096),
    "gqa_28_on_4_8192_causal": (2, 8192, 8192, 28, 4, 128, True, "bfloat16"),
    "window_300_on_1024": (2, 1024, 1024, 8, 8, 64, True, "bfloat16", 300),
    # past 1024 by block kind: an edge block beside every diagonal block,
    # and a q_offset of one block (2048 queries end-aligned on 3072 keys)
    "causal_4096_window_2048": (2, 4096, 4096, 8, 8, 64, True, "bfloat16",
                                2048),
    "causal_2048_on_3072_keys": (2, 2048, 3072, 16, 4, 128, True,
                                 "bfloat16"),
    # trinity_mini_ep16_8k_1chip's window layers: 8:1 grouped K/V, a window
    # two 1024-blocks wide
    "gqa_32_on_4_8192_window_2048": (2, 8192, 8192, 32, 4, 128, True,
                                     "bfloat16", 2048),
    # past the fused backward's VMEM budget for dQ: the pair
    "causal_49152_past_the_budget": (1, 49152, 49152, 1, 1, 128, True,
                                     "bfloat16"),
}


@pytest.mark.parametrize("call", sorted(_CALLS))
def test_forward_and_backward_compile_for_v5e(one_chip, fa, call):
    b, lq, lk, h, kv, d, causal, dtype, *window = _CALLS[call]
    window = window[0] if window else None

    def shape(length, heads):
        return jax.ShapeDtypeStruct((b, length, heads, d), jnp.dtype(dtype),
                                    sharding=one_chip)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal,
                                  window=window).astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(lq, h), shape(lk, kv), shape(lk, kv)).compile().as_text()
    assert _flash_kernels(hlo) == (PAIR if "past_the_budget" in call
                                   else FUSED)


def test_latent_attention_kernels_compile_for_v5e(one_chip, fa):
    """joyai_flash_ep32_8k_1chip's call: 2 x 8192 causal, 32 heads whose
    queries and keys are 192 wide and values 128 (a full-width block, no
    zero columns), by block kind; the output and dV at the value width."""
    b, length, h, dqk, dv = 2, 8192, 32, 192, 128

    def shape(width):
        return jax.ShapeDtypeStruct((b, length, h, width), jnp.bfloat16,
                                    sharding=one_chip)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True)
        assert out.shape == (b, length, h, dv)
        return out.astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(dqk), shape(dqk), shape(dv)).compile().as_text()
    assert _flash_kernels(hlo) == FUSED


@pytest.mark.parametrize("length,dqk,dv,causal,dtype", [
    (22528, 192, 128, True, "bfloat16"), (22528, 192, 128, False, "bfloat16"),
    (40960, 128, 128, True, "bfloat16"), (26624, 128, 128, True, "float32")])
def test_the_longest_fused_backward_compiles_for_v5e(one_chip, fa, length,
                                                     dqk, dv, causal, dtype):
    """The longest calls ``backward_path`` gives the one kernel, at two
    head widths, causal or not, in bfloat16 and float32: their dQ of a
    whole (batch, head) takes most of the budget, and the kernel still
    fits the scoped VMEM limit (past about 1.5 times the budget it does
    not: 34816 x 192 asks 65 MiB of 64)."""
    itemsize = jnp.dtype(dtype).itemsize
    assert fa.backward_path(length, dqk, itemsize) == ("bwd_dqkv",)
    assert fa.backward_path(length + 1024, dqk, itemsize) \
        == ("bwd_dq", "bwd_dkv")

    def shape(width):
        return jax.ShapeDtypeStruct((1, length, width), jnp.dtype(dtype),
                                    sharding=one_chip)
    lse = jax.ShapeDtypeStruct((1, length), jnp.float32, sharding=one_chip)
    hlo = jax.jit(lambda q, k, v, o, lse, do: fa._fa_backward(
        q, k, v, o, lse, do, causal, 0.1)).lower(
        shape(dqk), shape(dqk), shape(dv), shape(dv), lse,
        shape(dv)).compile().as_text()
    assert _flash_kernels(hlo) == {"hvd_flash_bwd_dqkv"}


# -- DroplessMoE's grouped products (PR 34) -----------------------------------

# (tokens, experts, a token, held, hidden, expert width, form, kernels): the
# expert layer of each sparse cell of BENCHMARK.json, at its real shapes,
# and how many kernels its six products make (two whose shapes and tiles
# are the same are one kernel)
_EXPERT_LAYERS = {
    "smallthinker_ep4_8k": (16384, 64, 6, 16, 2560, 768, "gated_relu", 6),
    "nemotron_tt_ep16_8k": (16384, 128, 6, 8, 2688, 1856, "relu2", 6),
    # both transposed products contract 2048 into blocks of 1024 columns
    "trinity_mini_ep16_8k": (16384, 128, 8, 8, 2048, 1024, "gated_silu", 5),
}


@pytest.mark.parametrize("cell", sorted(_EXPERT_LAYERS))
def test_no_grouped_product_of_a_cell_runs_a_128_wide_tile(one_chip,
                                                           monkeypatch, cell):
    """``jax.grad`` of ``parallel.moe._on_rows`` compiles for a v5e at the
    cell's shapes, and each of its grouped products (two forward, four
    transposes) runs in tiles of at least 256 in the contraction and in the
    output's width. The repo's kernels say their tile in their names
    (``hvd_gmm_<tm>x<contraction>x<tn>``, ``hvd_tgmm_<tm>x<tk>x<tn>``), the
    compiler's own product in ``ragged_dot_tiling="tm,tk,tn"``: it falls to
    128 where 256 does not divide a width (2688, 1856), at a sixth of the
    speed (PERF.md, PR 34). The guard that the next model's widths cannot
    bring that tile back in silence."""
    import re
    from horovod_tpu.ops.pallas import grouped_matmul as gmm
    from horovod_tpu.parallel import moe
    monkeypatch.setattr(gmm, "_interpret", lambda: False)
    tokens, routed, k, held, d, f, form, kernels = _EXPERT_LAYERS[cell]
    rows = moe.buffer_rows(tokens, k, held, routed)
    wide = moe.EXPERT_FORMS[form][1] * f

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def loss(xt, weights, w_in, w_down, order, inverse, sizes):
        return moe._on_rows(rows, k, form, xt, weights, w_in, w_down, order,
                            inverse, sizes).astype(jnp.float32).sum()
    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        shape((tokens, d), jnp.bfloat16), shape((tokens, k), jnp.float32),
        shape((held, d, wide), jnp.float32), shape((held, f, d), jnp.float32),
        shape((tokens * k,), jnp.int32), shape((tokens * k,), jnp.int32),
        shape((held,), jnp.int32)).compile().as_text()
    theirs = {tuple(map(int, t.split(","))) for t in
              re.findall(r'ragged_dot_tiling="([\d,]+)"', hlo)}
    ours = {(name, *map(int, dims)) for name, *dims in re.findall(
        r"(hvd_t?gmm(?:_t)?)_(\d+)x(\d+)x(\d+)", hlo)}
    # two products, each forward, for its left and for its right operand
    assert not theirs and len(ours) == kernels, (theirs, ours)
    assert {name for name, *_ in ours} == {"hvd_gmm", "hvd_gmm_t", "hvd_tgmm"}
    for *_, tk, tn in ours:
        assert min(tk, tn) >= 256, ours


# -- the Mamba-2 scan's kernels (PR 36) ---------------------------------------

def test_the_scans_three_kernels_compile_for_v5e(one_chip, monkeypatch):
    """``parallel.ssm.ssm_scan`` and its backward pass at the call of
    ``nemotron_tt_ep16_8k_1chip`` (2 x 8192, 64 heads of 64, state 128, 8
    groups, chunks of 128, bfloat16) lower and compile for a v5e: the
    forward sweep, the backward pass's states sweep and the reverse sweep,
    one kernel each, in blocks of 128 positions x 512 channels x 128 state
    columns. A slice off the (8, 128) tiling or too much scoped VMEM fails
    here and not on the chip."""
    from horovod_tpu.ops.pallas import ssm_scan as kernels
    from horovod_tpu.parallel import ssm
    monkeypatch.setattr(kernels, "_interpret", lambda: False)
    b, length, heads, head, groups, state, chunk = 2, 8192, 64, 64, 8, 128, 128
    assert ssm.scan_path((b, length, heads, head), groups, state, chunk, 2) \
        == (1, (128, 512, 128))

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def forward_and_backward(dy, *operands):
        y, pull = jax.vjp(lambda *a: ssm.ssm_scan(*a, chunk), *operands)
        return y, pull(dy)
    x = shape((b, length, heads, head))
    bc = shape((b, length, groups, state))
    per_head = shape((heads,), jnp.float32)
    hlo = jax.jit(forward_and_backward).lower(
        x, x, shape((b, length, heads), jnp.float32), per_head, bc, bc,
        per_head).compile().as_text()
    for kernel in ("hvd_ssm_fwd", "hvd_ssm_states", "hvd_ssm_bwd"):
        assert f"{kernel}_128x512x128" in hlo, \
            f"{kernel} is not in the compiled program"


# -- the delta rule's kernels (Kimi Delta Attention) --------------------------

def test_the_delta_rules_three_kernels_compile_for_v5e(one_chip, monkeypatch):
    """``ops.pallas.kda.kda`` and its backward pass at the call of
    ``kimi_linear_ep32_8k_1chip`` (2 x 8192, 32 heads of 128, bfloat16)
    lower and compile for a v5e in the kernels' chunks of 128: the
    forward sweep, the backward pass's states sweep and the reverse sweep
    with the chunk's VJP inside it, one kernel each. A slice off the (8,
    128) tiling, an op Mosaic cannot lower or too much scoped VMEM fails
    here and not on the chip."""
    from horovod_tpu.ops.pallas import kda as kernels
    from horovod_tpu.parallel import kda
    monkeypatch.setattr(kernels, "_interpret", lambda: False)
    b, length, heads, d = 2, 8192, 32, 128
    chunk = kernels.CHUNK
    assert kda.kda_path((b, length, heads, d), 2) == (1, chunk)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def forward_and_backward(do, *operands):
        o, pull = jax.vjp(lambda *a: kernels.kda(*a, chunk), *operands)
        return o, pull(do)
    x = shape((b, length, heads, d))
    hlo = jax.jit(forward_and_backward).lower(
        x, x, x, x, shape((b, length, heads, d), jnp.float32),
        shape((b, length, heads), jnp.float32)).compile().as_text()
    for kernel in ("hvd_kda_fwd", "hvd_kda_states", "hvd_kda_bwd"):
        assert f"{kernel}_{chunk}x128" in hlo, \
            f"{kernel} is not in the compiled program"


# -- attention's prologue pass (PR 38) ----------------------------------------

# (batch, length, heads, kv heads, normed, window or None for no rotation):
# the calls of the two cells whose layers take the pass, at their real
# shapes
_PROLOGUE_CALLS = {
    "trinity_mini_window_layer": (2, 8192, 32, 4, True, 2048),
    "trinity_mini_full_layer": (2, 8192, 32, 4, True, None),
    "smallthinker_window_layer": (2, 8192, 28, 4, False, 4096),
}


@pytest.mark.parametrize("call", sorted(_PROLOGUE_CALLS))
def test_the_prologue_kernels_compile_for_v5e(one_chip, fa, monkeypatch,
                                              call):
    """``ops/pallas/attn_prologue.py``'s ``attention`` forward and backward
    (its two kernels around the flash kernels) at the calls of
    ``trinity_mini_ep16_8k_1chip`` (32 query heads on 4, the norm with and
    without the rotation) and ``smallthinker_ep4_8k_1chip`` (28 on 4, the
    rotation alone) lowers and compiles for a v5e, in the row tile the rule
    picks (256). A slice off the (8, 128) tiling or too much scoped VMEM
    fails here and not on the chip."""
    from horovod_tpu.ops.pallas import attn_prologue as kernels
    from horovod_tpu.parallel import tp
    monkeypatch.setattr(kernels, "_interpret", lambda: False)
    b, length, heads, kv, normed, window = _PROLOGUE_CALLS[call]
    d = 128
    path, tile = tp.prologue_path((b, length, (heads + 2 * kv) * d), heads,
                                  kv, d, 2, flash=True, normed=normed,
                                  rotated=window is not None)
    assert (path, tile) == (1, 256)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def forward_and_backward(qkv, q_scale, k_scale, do):
        out, pull = jax.vjp(
            lambda *a: kernels.attention(*a, heads, kv,
                                         1e-5 if normed else None,
                                         1e4 if window else None, tile,
                                         True, window),
            qkv, q_scale, k_scale)
        return out, pull(do)
    scale = shape((d,), jnp.float32)
    hlo = jax.jit(forward_and_backward).lower(
        shape((b, length, (heads + 2 * kv) * d)), scale, scale,
        shape((b * heads, length, d))).compile().as_text()
    for kernel in ("hvd_attn_prologue_fwd", "hvd_attn_prologue_bwd"):
        assert kernel in hlo, f"{kernel} is not in the compiled program"
    assert _flash_kernels(hlo) == FUSED


@pytest.mark.parametrize("cell,taken", [
    ("gpt2m_1chip", False), ("nemotron_tt_ep16_8k_1chip", False),
    ("trinity_mini_ep16_8k_1chip", True),
    ("smallthinker_ep4_8k_1chip", True)])
def test_which_cells_steps_hold_the_prologue(one_chip, fa, monkeypatch, cell,
                                             taken):
    """The loss and gradient of each cell's model, lowered at its real size
    for a v5e: ``gpt2_medium`` (heads of 64, no norm, no positions) and
    ``nemotron_twotower_30b_a3b_ep16`` (no norm, no positions) hold no
    prologue kernel, so their steps are the parent's; the two models whose
    layers norm or rotate heads of 128 hold both."""
    import os
    from benchmark import run as bench
    from benchmark.harness import manifest, program, reference, traffic
    from horovod_tpu.ops.pallas import attn_prologue as kernels
    from horovod_tpu.ops.pallas import grouped_matmul as gmm
    from horovod_tpu.ops.pallas import ssm_scan
    for mod in (kernels, gmm, ssm_scan):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    _, workload, cfg = bench.load_cell(manifest.load(root), cell, False)
    model, loss_fn = program.load_model_builder(cfg["model"])(cfg)
    batch = traffic.Batches(cfg, workload, 7).next()
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(tuple(s), jnp.float32,
                                       sharding=one_chip),
        reference.param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    batch = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), batch)
    text = jax.jit(jax.grad(loss_fn)).lower(params, batch).as_text()
    assert "hvd_flash_fwd" in text
    for kernel in ("hvd_attn_prologue_fwd", "hvd_attn_prologue_bwd"):
        assert (kernel in text) == taken, kernel
