"""What decides ``correct``: the program's first three steps against the
plain reference's.

Numbers compared (each has a limit of its own in the cell's file):

- ``loss_gap``: |program's loss - reference's| / |reference's|, the worst
  of the first three steps.
- ``grad_gap``: worst leaf of the first gradient as the optimizer got it
  (Adam's first moment after step one, over 1 - b1): the gap between the
  program's norm of the leaf and the reference's, over the reference's norm
  of that leaf or of the median leaf, whichever is larger.
- ``delta_gap``: the same measure on the parameters' change after three
  steps. Leaves whose reference gradient is under a thousandth of the
  median leaf's are left out: Adam moves them by round-off alone.
"""

import math
import statistics

import jax
import jax.numpy as jnp

from . import weights

CHECK_STEPS = 3
NUMBERS = ("loss_gap", "grad_gap", "delta_gap")
TINY_GRADIENT = 1e-3


class Norms:
    """Euclidean norms of a parameter-shaped tree, leaf by leaf, as
    {name: float}. A leaf that fuses several matrices (``fused``: path ->
    number of equal parts along the last axis, as the reference states them)
    gives one norm per part, named ``path#i``: the key's part of a fused
    qkv bias has no gradient under softmax, and only apart from q and v can
    the rule on tiny gradients see it."""

    def __init__(self, shapes, cfg, fused):
        fresh = weights.params_fn(shapes, cfg)

        def norms(tree):
            out = {}
            for path, leaf in weights.flatten(plain(tree)):
                leaf = leaf.astype(jnp.float32)
                parts = jnp.split(leaf, fused[path], -1) \
                    if path in fused else [leaf]
                for i, part in enumerate(parts):
                    name = "/".join(path) + (f"#{i}" if path in fused else "")
                    out[name] = jnp.sqrt(jnp.sum(jnp.square(part)))
            return out

        self._of = jax.jit(norms)
        self._of_change = jax.jit(lambda params, key: norms(
            jax.tree.map(jnp.subtract, plain(params), fresh(key))))

    def of(self, tree, scale=1.0):
        return {k: float(v) * scale
                for k, v in jax.device_get(self._of(tree)).items()}

    def of_change(self, params, seed):
        """Norms of ``params`` minus the seed's fresh parameters."""
        out = self._of_change(params, weights.key(seed))
        return {k: float(v) for k, v in jax.device_get(out).items()}


def find_adam_mu(opt_state):
    """Adam's first moment inside an optax state, wherever the chain put
    it."""
    found = []

    def visit(node):
        if hasattr(node, "mu") and hasattr(node, "nu"):
            found.append(node.mu)
        elif isinstance(node, (tuple, list)):
            for child in node:
                visit(child)

    visit(opt_state)
    if len(found) != 1:
        raise ValueError(f"expected one Adam state, found {len(found)}")
    return found[0]


class ProgramReadings:
    """Reads, from the program's own state between its first steps, the
    norms the comparison needs. The state is only read, never replaced."""

    def __init__(self, norms, seed, cfg):
        self.norms, self.seed = norms, seed
        self.b1 = cfg["assumed"]["adam_b1"]
        self.losses, self.grad_norms, self.delta_norms = [], None, None

    def after_step(self, k, state, loss):
        """Called with the state step ``k`` (1-based) returned, before the
        next step donates it."""
        self.losses.append(float(loss))
        if k == 1:
            self.grad_norms = self.norms.of(find_adam_mu(state.opt_state),
                                            1.0 / (1.0 - self.b1))
        if k == CHECK_STEPS:
            self.delta_norms = self.norms.of_change(state.params, self.seed)

    def asdict(self):
        return {"losses": self.losses, "grad_norms": self.grad_norms,
                "delta_norms": self.delta_norms}


def plain(tree):
    """Nested plain dicts out of flax's (frozen or not) dicts."""
    if hasattr(tree, "items"):
        return {k: plain(v) for k, v in tree.items()}
    return tree


def reference_readings(ref, norms, shapes, seed, cfg, batches):
    """The reference's first three steps on ``batches`` (host batches, in
    order): losses, first-gradient norms, parameter-change norms."""
    params = weights.make_params(shapes, seed, cfg)
    opt = ref.init_opt(params)
    losses, grad_norms = [], None
    for k, batch in enumerate(batches[:CHECK_STEPS], start=1):
        loss, grads = ref.loss_and_grad(params, batch)
        losses.append(float(loss))
        if k == 1:
            grad_norms = norms.of(grads)
        params, opt = ref.adam(params, grads, opt)
        del grads
    del opt
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": norms.of_change(params, seed)}


def leaf_gaps(program, reference, leave_out=()):
    """{leaf: |program's norm - reference's| / max(reference's norm of the
    leaf, of the median leaf)} over the leaves not left out."""
    if sorted(program) != sorted(reference):
        raise ValueError("program and reference name different leaves")
    median = statistics.median(reference.values())
    return {name: abs(program[name] - ref) / max(ref, median)
            for name, ref in reference.items() if name not in leave_out}


def worst(gaps):
    """(gap, leaf) of the largest gap; a nan counts as largest."""
    worst, where = 0.0, None
    for name, gap in gaps.items():
        if not gap <= worst:
            worst, where = gap, name
    return worst, where


def compare(program, reference, limits):
    """The numbers compared, each beside its limit, and whether all hold.

    ``program`` and ``reference`` carry ``losses``, ``grad_norms`` and
    ``delta_norms``. Returns (correct, rows) with rows of
    {name, value, limit, ok, where}.
    """
    rows = []

    def row(name, value, where=None):
        limit = limits[name]
        ok = math.isfinite(value) and value <= limit
        rows.append({"name": name, "value": value, "limit": limit,
                     "ok": ok, "where": where})

    loss_gaps = {f"step_{k}": abs(lp - lr) / abs(lr) for k, (lp, lr)
                 in enumerate(zip(program["losses"], reference["losses"]),
                              start=1)}
    row("loss_gap", *worst(loss_gaps))
    row("grad_gap", *worst(leaf_gaps(program["grad_norms"],
                                     reference["grad_norms"])))
    median = statistics.median(reference["grad_norms"].values())
    tiny = {n for n, g in reference["grad_norms"].items()
            if g < TINY_GRADIENT * median}
    row("delta_gap", *worst(leaf_gaps(
        program["delta_norms"], reference["delta_norms"], leave_out=tiny)))
    rows[-1]["left_out"] = sorted(tiny)
    return all(r["ok"] for r in rows), rows
