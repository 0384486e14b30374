"""Expert parallelism: switch-style Mixture-of-Experts with AllToAll dispatch.

The reference exposes AllToAll with negotiated uneven splits as a raw
primitive (reference: horovod/common/operations.cc:1930 EnqueueTensorAlltoall,
collective_operations.h:199-268) — the exact communication pattern MoE
dispatch needs — but ships no MoE layer (SURVEY.md §2.6: EP absent as a
strategy). This module builds the strategy TPU-first:

- **Static shapes**: capacity-based dispatch (Switch Transformer style).
  Every expert receives exactly ``capacity`` token slots per source shard;
  overflow tokens are dropped (their residual path passes through). No
  dynamic shapes, so the whole layer jits into one XLA program and the
  dispatch einsums run on the MXU.
- **EP over a mesh axis**: experts are sharded across ``ep``; two
  ``lax.all_to_all``s over ICI move token slots to their expert's shard and
  back — the MoE realization of the reference's alltoall primitive.
- **Router**: top-1 (switch) or top-2 gating with the standard
  load-balancing auxiliary loss (fraction-of-tokens x mean-probability).

Call (and init) inside ``shard_map`` with the ``ep`` axis bound; outside an
axis context the layer degrades to ep=1 (all experts local), which is the
correctness oracle used in tests.

:class:`DroplessMoE` is the second layer of this file, for the fine-grained
mixtures of 2025's open models (64 experts, 6 a token): many-of-many routing
with no capacity and no dropped token, gated experts, a router that may
read another tensor than the experts do, and a layer that is TOLD which
contiguous run of the experts it holds and computes their part of the sum.
Dispatch is a sort of the (token, choice) pairs by expert and the experts'
products are grouped over the experts held (:func:`_grouped_dot`), so no
``(T, E, C)`` tensor exists.
"""

import functools
import math
from typing import Any, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.ops.pallas import grouped_matmul as gmm
from horovod_tpu.parallel.tp import axis_size_or_1, shard_init
from horovod_tpu.trace.scopes import scope

EP_AXIS = "ep"


def _hier_dispatch(slots, axis_name, num_slices, cross_label):
    """Expert-major ``(E, C, d)`` slots -> source-major ``(e_local, n*C,
    d)`` via the 2-level alltoall: the reference's split0/concat1 tiled
    exchange reduces to the canonical split0/concat0 form plus a local
    transpose, which then decomposes into slice-local (ICI) and
    cross-slice (DCN, optionally block-scaled) legs
    (``strategies.alltoall_tiered_groups``). Bit-equivalent to the flat
    ``lax.all_to_all`` route UNLESS the cross leg quantizes."""
    from horovod_tpu.parallel.strategies import alltoall_tiered_groups
    n = int(lax.axis_size(axis_name))
    E, C, d = slots.shape
    e_local = E // n
    z = alltoall_tiered_groups(slots, axis_name, num_slices,
                               cross_wire=cross_label)
    return z.reshape(n, e_local, C, d).transpose(1, 0, 2, 3) \
            .reshape(e_local, n * C, d)


def _hier_combine(y, axis_name, num_slices, cross_label):
    """Inverse of :func:`_hier_dispatch`: source-major ``(e_local, n*C,
    d)`` expert outputs back to the expert-major ``(E, C, d)`` layout,
    through the same 2-level exchange."""
    from horovod_tpu.parallel.strategies import alltoall_tiered_groups
    n = int(lax.axis_size(axis_name))
    e_local, nC, d = y.shape
    C = nC // n
    z = y.reshape(e_local, n, C, d).transpose(1, 0, 2, 3) \
         .reshape(n * e_local, C, d)
    return alltoall_tiered_groups(z, axis_name, num_slices,
                                  cross_wire=cross_label)


def _router(x, probs, k: int, capacity: int):
    """Compute dispatch/combine tensors for top-k capacity routing.

    Args:
      x: (T, d) local tokens.  probs: (T, E) router probabilities.
    Returns:
      dispatch (T, E, C) one-hot, combine (T, E, C) gated weights, aux loss.
    """
    T, E = probs.shape
    gate_vals, expert_idx = lax.top_k(probs, k)           # (T, k)
    # Renormalize the selected gates so they sum to 1 per token (top-2 case).
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    dispatch = jnp.zeros((T, E, capacity), probs.dtype)
    combine = jnp.zeros((T, E, capacity), probs.dtype)
    # Process the k choices in priority order; capacity positions are
    # assigned first-come-first-served in token order per expert.
    used = jnp.zeros((E,), jnp.int32)
    for j in range(k):
        e = expert_idx[:, j]                               # (T,)
        onehot = jax.nn.one_hot(e, E, dtype=jnp.int32)     # (T, E)
        # Position of each token within its expert's queue for this choice.
        pos_in_e = jnp.cumsum(onehot, axis=0) - onehot + used[None, :]
        pos = jnp.sum(pos_in_e * onehot, -1)               # (T,)
        keep = pos < capacity
        slot = jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity,
                              dtype=probs.dtype)           # (T, C), 0 if drop
        d_j = jax.nn.one_hot(e, E, dtype=probs.dtype)[..., None] \
            * slot[:, None, :]                             # (T, E, C)
        dispatch = dispatch + d_j
        combine = combine + d_j * gate_vals[:, j, None, None]
        used = used + jnp.sum(onehot * keep[:, None].astype(jnp.int32), 0)

    # Load-balancing loss (Switch Transformer eq. 4): E * sum_e f_e * P_e,
    # computed on the top-1 assignment.
    top1 = jax.nn.one_hot(expert_idx[:, 0], E, dtype=probs.dtype)
    f = jnp.mean(top1, axis=0)
    P = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * P)
    return dispatch, combine, aux


class MoEMlp(nn.Module):
    """Expert-parallel MoE feed-forward layer (drop-in for a dense MLP).

    ``num_experts`` is global; each ep shard owns ``num_experts / ep``
    experts' weights. Returns ``(y, aux_loss)``.
    """
    num_experts: int
    hidden_size: int
    intermediate_size: int
    k: int = 1
    capacity_factor: float = 2.0
    dtype: Any = jnp.float32
    axis_name: Optional[str] = EP_AXIS
    # Hierarchical expert dispatch: None = auto (the
    # HOROVOD_HIERARCHICAL_ALLTOALL / a2a strategy registry chain via
    # strategies.a2a_hierarchy_for), True = force when a slice hierarchy
    # exists, False = always flat.
    hierarchical: Optional[bool] = None

    @nn.compact
    def __call__(self, x):
        n = axis_size_or_1(self.axis_name)
        E, d, f = self.num_experts, self.hidden_size, self.intermediate_size
        if E % n != 0:
            raise ValueError(f"num_experts {E} not divisible by ep={n}")
        e_local = E // n
        orig_shape = x.shape
        xt = x.reshape(-1, d)                              # (T, d)
        T = xt.shape[0]
        capacity = max(1, int(self.capacity_factor * self.k * T / E))

        # Router in fp32 for stable softmax.
        logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                          name="router")(xt.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        dispatch, combine, aux = _router(xt, probs, self.k, capacity)

        # (T, E, C) x (T, d) -> (E, C, d): expert-major token slots.
        slots = jnp.einsum("tec,td->ecd", dispatch.astype(self.dtype),
                           xt.astype(self.dtype))

        hier = None
        if n > 1:
            from horovod_tpu.parallel.strategies import (
                _record_jit_a2a_flat, a2a_hierarchy_for)
            hier = a2a_hierarchy_for(self.axis_name, self.hierarchical)

        if n > 1 and hier is not None:
            # 2-level route: slice-local a2a (ICI) + cross-slice leg on
            # the per-tier wire (DCN) — expert dispatch pays DCN only for
            # genuinely cross-slice token slots.
            slots = _hier_dispatch(slots, self.axis_name, hier[0], hier[1])
        elif n > 1:
            # Send each expert block to its owner shard; receive all source
            # shards' slots for OUR local experts: (E, C, d) -> (e_local,
            # n*C, d), source-major along the slot axis. Tiled all_to_all is
            # a pure inter-device transpose — no reshapes, clean transpose
            # rule for AD.
            _record_jit_a2a_flat(slots, n)
            slots = lax.all_to_all(slots, self.axis_name, split_axis=0,
                                   concat_axis=1, tiled=True)
        else:
            slots = slots.reshape(e_local, capacity, d)

        # Each ep shard draws its own experts; the router above stays
        # replicated (axis-invariant) under the same init rng.
        w_in = self.param("w_in",
                          shard_init(nn.initializers.lecun_normal(),
                                     self.axis_name),
                          (e_local, d, f), jnp.float32)
        w_out = self.param("w_out",
                           shard_init(nn.initializers.lecun_normal(),
                                      self.axis_name),
                           (e_local, f, d), jnp.float32)
        h = jnp.einsum("ecd,edf->ecf", slots,
                       jnp.asarray(w_in, self.dtype))
        h = nn.gelu(h)
        y = jnp.einsum("ecf,efd->ecd", h, jnp.asarray(w_out, self.dtype))

        if n > 1 and hier is not None:
            y = _hier_combine(y, self.axis_name, hier[0], hier[1])
        elif n > 1:
            # Inverse transpose: source-major slots go back to their source
            # shard, restoring the expert-major (E, C, d) layout.
            _record_jit_a2a_flat(y, n)
            y = lax.all_to_all(y, self.axis_name, split_axis=1,
                               concat_axis=0, tiled=True)

        out = jnp.einsum("tec,ecd->td", combine.astype(self.dtype), y)
        return out.reshape(orig_shape), aux


# DroplessMoE's buffer of rows: room for SLACK times the rows expected live,
# in whole row tiles of the grouped product. Constants, not options: random
# routing keeps the live rows within 1 % of expected and a training router
# moved them by about 12 % (PERF.md, PR 29); what 1.5 does not cover takes
# the overflow path, which is exact, so no value is wrong, only slower.
SLACK = 1.5
ROW_TILE = 512


def buffer_rows(tokens, per_token, held, routed):
    """Rows of the buffer a DroplessMoE call moves: ``SLACK`` times the
    ``tokens * per_token * held / routed`` expected live, rounded up to
    ``ROW_TILE``, and never more than one a (token, choice) pair."""
    pairs = tokens * per_token
    expected = math.ceil(SLACK * pairs * held / routed)
    return min(pairs, -(-expected // ROW_TILE) * ROW_TILE)


class _Rows(NamedTuple):
    """Where the rows of a buffer of sorted pairs belong: row r is pair
    ``pair[r]`` of token ``tok[r]``; choice j of token t sorted to row
    ``at[j, t]`` and is held here if ``held[j, t]``. Choice-major, (k, T):
    a (T, k, d) array would carry k in its tiled second-minor dimension,
    and every reshape to it is a copy. Rows no held choice points at (the
    grouped products leave them unwritten: they may hold anything, NaN
    included) are read by nothing that reads through ``at`` under
    ``held``."""
    pair: Any
    tok: Any
    at: Any
    held: Any


def _rows(rows, k, order, inverse, n_live):
    pair = order[:rows]
    at = inverse.reshape(-1, k).T
    # A pair not held sorted behind the live ones: whichever row it reads
    # is masked, and ``%`` spreads those reads over the buffer (all on one
    # row they were a tenth slower).
    return _Rows(pair, pair // k, at % rows, at < n_live)


def _sum_to_tokens(buf, weights, where):
    """Float32 (T, d): for every token the sum over the choices held here
    of the row its pair sorted to, times the choice's weight where
    ``weights`` (k, T) are given: k gathers of T rows in ``buf``'s dtype,
    one choice at a time (all k at once keep a (k, T, d) array alive:
    0.9 GiB more a step at 2 x 8192 tokens). A scatter-add of the
    buffer's rows into (T, d) was the slower on the chip (6.0 ms against
    5.1 for 36,864 rows, 14.1 against 5.1 for 98,304: PERF.md, PR 30)."""
    out = jnp.zeros((where.at.shape[1], buf.shape[-1]), jnp.float32)
    for j, (at, held) in enumerate(zip(where.at, where.held)):
        picked = jnp.where(held[:, None], buf[at], 0).astype(jnp.float32)
        if weights is not None:
            picked = picked * weights[j][:, None]
        out = out + picked
    return out


@jax.custom_vjp
def _take_rows(x, where):
    """The buffer: row r is token ``where.tok[r]``'s row of ``x`` (T, d).
    The transpose of this gather is a scatter-add; the gradient is
    written as the sum by token instead, which leaves out the rows of no
    expert held here."""
    return x[where.tok]


def _take_rows_fwd(x, where):
    return x[where.tok], where


def _take_rows_bwd(where, g):
    return _sum_to_tokens(g, None, where).astype(g.dtype), None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@jax.custom_vjp
def _combine(y, weights, where):
    """(T, d) in ``y``'s dtype: each token's float32 sum of its experts'
    outputs ``y`` (the buffer's rows) under ``weights`` (T, k)."""
    return _sum_to_tokens(y, weights.T, where).astype(y.dtype)


def _combine_fwd(y, weights, where):
    return _combine(y, weights, where), (y, weights, where)


def _combine_bwd(res, g):
    y, weights, where = res
    g = g[where.tok].astype(jnp.float32)
    g_y = weights.reshape(-1)[where.pair][:, None] * g
    g_weights = jnp.sum(g * y.astype(jnp.float32), -1)
    g_weights = jnp.where(where.held, g_weights[where.at], 0).T
    return g_y.astype(y.dtype), g_weights, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _gated(act):
    def form(h):
        gate, up = jnp.split(h, 2, axis=-1)
        return act(gate) * up
    return form


# What an expert is, by the name a layer is given as ``expert_form``: the
# name of its first matrix, that matrix's width in units of the expert's,
# and what lies between the two products.
EXPERT_FORMS = {"gated_relu": ("w_gate_up", 2, _gated(nn.relu)),
                "relu2": ("w_up", 1, lambda h: jnp.square(nn.relu(h))),
                "gated_silu": ("w_gate_up", 2, _gated(nn.silu))}
WEIGHTINGS = ("softmax", "sigmoid")


def product_tiles(m, k, n, itemsize=2):
    """``(path, (tm, tk, tn))`` of the grouped product ``(m, k) @ (groups,
    k, n)`` and of its two transposes, read off the shapes. Path 1: the
    repo's kernels (``ops/pallas/grouped_matmul.py``) in the tiles they
    pick from the shapes. Path 0, ``lax.ragged_dot`` as the TPU compiler
    tiles it (512 rows by, in k and in n, the largest of 512, 256 and 128
    that DIVIDES the width: a seventh of the kernels' speed where that is
    128; PERF.md, PR 34), only where no slab of the kernels' fits VMEM: a
    contraction of some 25,000 in bfloat16."""
    tiles = gmm.pick_tiles(m, k, n, itemsize)
    if tiles is not None:
        return 1, tiles
    return 0, (ROW_TILE, *(next((t for t in (512, 256) if w % t == 0), 128)
                           for w in (k, n)))


def _grouped_dot(lhs, rhs, sizes):
    """(m, n): row r of ``lhs`` (m, k) times ``rhs[g]`` (groups, k, n) for
    the group g that ``sizes`` puts r in, in ``lhs``'s dtype with float32
    sums; rows behind the last group may hold anything, here and in
    ``lhs``'s gradient. One rule picks how: :func:`product_tiles`."""
    path, tiles = product_tiles(lhs.shape[0], *rhs.shape[1:],
                                lhs.dtype.itemsize)
    if path == 0:
        return lax.ragged_dot(lhs, rhs, sizes)
    return gmm.grouped_matmul(lhs, rhs, sizes, tiles)


def _on_rows(rows, k, form, xt, weights, w_in, w_down, order, inverse, sizes):
    """The experts' weighted outputs summed by token, (T, d), from the
    first ``rows`` of the sorted pairs, which must hold every live one:
    their rows out of ``xt``, through the grouped products of experts of
    ``form``, back into their tokens."""
    with scope("moe.dispatch"):
        where = _rows(rows, k, order, inverse, jnp.sum(sizes))
        buf = _take_rows(xt, where)
    with scope("moe.experts"):
        h = _grouped_dot(buf, jnp.asarray(w_in, xt.dtype), sizes)
        y = _grouped_dot(EXPERT_FORMS[form][2](h),
                         jnp.asarray(w_down, xt.dtype), sizes)
    with scope("moe.combine"):
        return _combine(y, weights, where)


def _where_they_fit(fn, rows, order, sizes, *operands):
    """``fn(rows, *operands)`` where the live pairs fit in ``rows``, else
    ``fn`` over every pair: chosen on the device."""
    return lax.cond(jnp.sum(sizes) <= rows, functools.partial(fn, rows),
                    functools.partial(fn, order.shape[0]), *operands)


# Jitted, with every choice static, so that the layers of a model, which
# call these with the same shapes, trace and lower each once: the two
# branches, forward and back, are most of what a layer gives the tracer.
@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _forward_where_they_fit(rows, k, form, *args):
    order, _, sizes = args[4:]
    return _where_they_fit(lambda n, *args: _on_rows(n, k, form, *args),
                           rows, order, sizes, *args)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _backward_where_they_fit(rows, k, form, g, *args):
    diff, (order, inverse, sizes) = args[:4], args[4:]

    def pull(n, g, *diff):
        return jax.vjp(lambda *diff: _on_rows(n, k, form, *diff, order,
                                              inverse, sizes), *diff)[1](g)
    return _where_they_fit(pull, rows, order, sizes, g, *diff)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _on_rows_expected(rows, k, form, xt, weights, w_in, w_down, order,
                      inverse, sizes):
    """:func:`_on_rows` over ``rows`` pairs where the live ones fit, else
    over all of them: one conditional going forward and one coming back,
    each branch keeping its temporaries to itself. (Differentiated as it
    stands, the forward conditional would hand the residuals of both
    branches, the untaken one's as zeros, to the backward one: 3.4 GiB
    more at 2 x 8192 tokens.) So the backward branch computes its forward
    part again from the arguments, which is what the caller's ``remat``
    would have done."""
    return _forward_where_they_fit(rows, k, form, xt, weights, w_in, w_down,
                                   order, inverse, sizes)


def _on_rows_expected_fwd(rows, k, form, *args):
    return _on_rows_expected(rows, k, form, *args), args


def _on_rows_expected_bwd(rows, k, form, args, g):
    return (*_backward_where_they_fit(rows, k, form, g, *args),
            None, None, None)


_on_rows_expected.defvjp(_on_rows_expected_fwd, _on_rows_expected_bwd)


class DroplessMoE(nn.Module):
    """Sparse feed-forward layer of small experts, ``top_k`` of
    ``num_experts`` a token, none dropped, for a layer that holds
    ``experts_held`` contiguous experts from ``first_expert`` on (all of
    them by default).

    The router (``num_experts`` wide, float32) reads ``router_input``
    (``x`` when None) and gives every expert a score; a token goes to the
    ``top_k`` experts of the largest score. ``weighting`` says how the
    chosen are weighed:

    - ``"softmax"``: the score is the logit, the weights the softmax of
      the chosen logits;
    - ``"sigmoid"``: the score is the logit's sigmoid, the weights the
      chosen scores over their sum;

    either way times ``weight_scale``. ``expert_form`` says what an
    expert is:

    - ``"gated_relu"``: ``(relu(x W_gate) * (x W_up)) W_down``, the first
      two fused as ``w_gate_up`` (held, d, 2 f);
    - ``"relu2"``: ``relu(x W_up)^2 W_down``, not gated, ``w_up``
      (held, d, f);
    - ``"gated_silu"``: ``(silu(x W_gate) * (x W_up)) W_down``, fused as
      ``w_gate_up`` like the first.

    ``selection_bias`` (a call argument, (num_experts,) float32, None for
    none) is added to the scores for the CHOICE of the ``top_k`` alone,
    under ``stop_gradient``: the weights come from the chosen experts'
    unbiased scores, and the bias gets no gradient. It is an input, not a
    parameter: who moves it against the experts' loads (a training
    recipe's rule) owns it.

    The layer returns ``sum over the chosen experts held here of w_e *
    expert_e(x)``: the whole layer when it holds every expert, else this
    share's partial sum, which the shares of the other holders complete
    (summed by the caller's exchange; on one chip there is none and
    nothing stands in for it).

    The (token, choice) pairs are sorted by expert, those routed elsewhere
    behind the last expert held, and the layer moves a buffer of the first
    ``buffer_rows`` of them: out of ``x``, through the grouped products,
    and summed back into their tokens. A share whose live pairs outnumber
    that buffer in some call (a skewed router) runs the same code on all
    T * top_k pairs instead, chosen on the device from the count of live
    pairs, so total imbalance drops nothing; a layer that holds every
    expert has the one size and no branch.
    """
    num_experts: int
    top_k: int
    hidden_size: int
    intermediate_size: int
    experts_held: Optional[int] = None
    first_expert: int = 0
    dtype: Any = jnp.float32
    weighting: str = "softmax"
    weight_scale: float = 1.0
    expert_form: str = "gated_relu"

    @nn.compact
    def __call__(self, x, router_input=None, selection_bias=None):
        E, k = self.num_experts, self.top_k
        d, f = self.hidden_size, self.intermediate_size
        held = E if self.experts_held is None else self.experts_held
        if not 0 < k <= E or held < 1 or self.first_expert < 0 \
                or self.first_expert + held > E:
            raise ValueError(
                f"top_k {k} of {E} experts, holding {held} from "
                f"{self.first_expert}: not a share of the experts")
        if self.weighting not in WEIGHTINGS:
            raise ValueError(f"unknown weighting {self.weighting!r}; "
                             f"choose from {WEIGHTINGS}")
        if self.expert_form not in EXPERT_FORMS:
            raise ValueError(f"unknown expert_form {self.expert_form!r}; "
                             f"choose from {tuple(EXPERT_FORMS)}")
        xt = x.reshape(-1, d)
        rt = xt if router_input is None else router_input.reshape(-1, d)
        T = xt.shape[0]
        C = buffer_rows(T, k, held, E)
        from horovod_tpu.metrics import instruments as hvd_metrics
        itemsize = jnp.dtype(self.dtype).itemsize
        wide = EXPERT_FORMS[self.expert_form][1] * f
        hvd_metrics.record_moe_layer(
            E, held, k, C, T,
            products={"in": product_tiles(C, d, wide, itemsize),
                      "down": product_tiles(C, f, d, itemsize)})

        with scope("moe.route"):
            logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                              precision=lax.Precision.HIGHEST,
                              name="router")(rt.astype(jnp.float32))
            scores = logits if self.weighting == "softmax" \
                else jax.nn.sigmoid(logits)
            if selection_bias is None:
                top, chosen = lax.top_k(scores, k)                 # (T, k)
            else:
                _, chosen = lax.top_k(scores + lax.stop_gradient(
                    jnp.asarray(selection_bias, jnp.float32)), k)
                top = jnp.take_along_axis(scores, chosen, axis=-1)
            weights = jax.nn.softmax(top, axis=-1) \
                if self.weighting == "softmax" \
                else top / jnp.sum(top, axis=-1, keepdims=True)
            if self.weight_scale != 1.0:
                weights = weights * self.weight_scale

        with scope("moe.dispatch"):
            local = chosen - self.first_expert
            here = (local >= 0) & (local < held)                   # (T, k)
            # Pairs routed elsewhere sort behind the last expert held.
            group = jnp.where(here, local, held).reshape(-1)
            order = jnp.argsort(group, stable=True)
            inverse = jnp.argsort(order)
            sizes = jnp.sum(group[:, None] == jnp.arange(held), 0,
                            dtype=jnp.int32)

        w_in = self.param(EXPERT_FORMS[self.expert_form][0],
                          nn.initializers.lecun_normal(),
                          (held, d, wide), jnp.float32)
        w_down = self.param("w_down", nn.initializers.lecun_normal(),
                            (held, f, d), jnp.float32)

        args = (self.expert_form, xt.astype(self.dtype), weights, w_in,
                w_down, order, inverse, sizes)
        if C == T * k:
            out = _on_rows(C, k, *args)
        else:
            out = _on_rows_expected(C, k, *args)
        return out.reshape(x.shape)
