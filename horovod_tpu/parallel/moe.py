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
products are grouped over the experts held (``lax.ragged_dot``), so no
``(T, E, C)`` tensor exists.
"""

import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.parallel.tp import axis_size_or_1, shard_init

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


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dispatch(x, order, inverse, n_live, k):
    """Rows of ``x`` (T, d) in the order of the sorted (token, choice)
    pairs: row r is token ``order[r] // k``. The transpose of this gather is
    a scatter-add over T * k rows; ``order`` is a permutation, so the
    gradient is written as the gather by its inverse and a sum over a
    token's k choices. Rows from ``n_live`` on belong to no expert held
    here and bring no gradient (the grouped products leave them
    unwritten)."""
    return x[order // k]


def _dispatch_fwd(x, order, inverse, n_live, k):
    return x[order // k], (inverse, n_live)


def _dispatch_bwd(k, res, g):
    inverse, n_live = res
    live = jnp.arange(g.shape[0])[:, None] < n_live
    g = jnp.where(live, g, 0)[inverse]
    return g.reshape(-1, k, g.shape[-1]).sum(1), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _undo_dispatch(y, order, inverse):
    """Sorted rows (T * k, d) back in (token, choice) order; the gradient
    is the gather by ``order``, as above."""
    return y[inverse]


def _undo_dispatch_fwd(y, order, inverse):
    return y[inverse], order


def _undo_dispatch_bwd(order, g):
    return g[order], None, None


_undo_dispatch.defvjp(_undo_dispatch_fwd, _undo_dispatch_bwd)


class DroplessMoE(nn.Module):
    """Sparse feed-forward layer of gated experts, ``top_k`` of
    ``num_experts`` a token, none dropped, for a layer that holds
    ``experts_held`` contiguous experts from ``first_expert`` on (all of
    them by default).

    The router (``num_experts`` wide, float32) reads ``router_input``
    (``x`` when None); a token's weights are the softmax of its ``top_k``
    largest logits. The layer returns ``sum over the chosen experts held
    here of w_e * (relu(x W_gate,e) * (x W_up,e)) W_down,e``: the whole
    layer when it holds every expert, else this share's partial sum, which
    the shares of the other holders complete (summed by the caller's
    exchange; on one chip there is none and nothing stands in for it).

    Every (token, choice) pair gets a row of the grouped products' buffer,
    T * top_k rows, so total imbalance drops nothing; the rows of pairs
    routed elsewhere are sorted behind the held experts' groups, where the
    grouped product does not visit them.
    """
    num_experts: int
    top_k: int
    hidden_size: int
    intermediate_size: int
    experts_held: Optional[int] = None
    first_expert: int = 0
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, router_input=None):
        E, k = self.num_experts, self.top_k
        d, f = self.hidden_size, self.intermediate_size
        held = E if self.experts_held is None else self.experts_held
        if not 0 < k <= E or held < 1 or self.first_expert < 0 \
                or self.first_expert + held > E:
            raise ValueError(
                f"top_k {k} of {E} experts, holding {held} from "
                f"{self.first_expert}: not a share of the experts")
        xt = x.reshape(-1, d)
        rt = xt if router_input is None else router_input.reshape(-1, d)
        T = xt.shape[0]
        from horovod_tpu.metrics import instruments as hvd_metrics
        hvd_metrics.record_moe_layer(E, held, k, T * k, T)

        with jax.named_scope("moe.route"):
            logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                              precision=lax.Precision.HIGHEST,
                              name="router")(rt.astype(jnp.float32))
            top, chosen = lax.top_k(logits, k)                     # (T, k)
            weights = jax.nn.softmax(top, axis=-1)

        with jax.named_scope("moe.dispatch"):
            local = chosen - self.first_expert
            here = (local >= 0) & (local < held)                   # (T, k)
            # Pairs routed elsewhere sort behind the last expert held.
            group = jnp.where(here, local, held).reshape(-1)
            order = jnp.argsort(group, stable=True)
            inverse = jnp.zeros_like(order).at[order].set(
                jnp.arange(T * k, dtype=order.dtype))
            sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
            rows = _dispatch(xt.astype(self.dtype), order, inverse,
                             jnp.sum(sizes), k)

        w_gate_up = self.param("w_gate_up", nn.initializers.lecun_normal(),
                               (held, d, 2 * f), jnp.float32)
        w_down = self.param("w_down", nn.initializers.lecun_normal(),
                            (held, f, d), jnp.float32)
        with jax.named_scope("moe.experts"):
            h = lax.ragged_dot(rows, jnp.asarray(w_gate_up, self.dtype),
                               sizes)
            gate, up = jnp.split(h, 2, axis=-1)
            y = lax.ragged_dot(nn.relu(gate) * up,
                               jnp.asarray(w_down, self.dtype), sizes)

        with jax.named_scope("moe.combine"):
            y = _undo_dispatch(y, order, inverse).reshape(T, k, d)
            y = jnp.where(here[..., None], y, 0)
            out = jnp.einsum("tk,tkd->td", weights, y.astype(jnp.float32))
        return out.astype(self.dtype).reshape(x.shape)
