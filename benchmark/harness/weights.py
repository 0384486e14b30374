"""Weights from the seed, made on the device in one jitted call.

The benchmark makes the weights, not the program: the program's
``model.init`` only says which names and shapes it wants, and the plain
reference states the same names and shapes on its own
(``reference.param_shapes``). Both sides get their values from here.
"""

import jax
import jax.numpy as jnp

from . import arch


def key(seed):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def flatten(tree, prefix=()):
    """Nested dict -> list of (path tuple, leaf), in sorted-key order."""
    out = []
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            out.extend(flatten(v, prefix + (k,)))
        else:
            out.append((prefix + (k,), v))
    return out


def unflatten(items):
    tree = {}
    for path, leaf in items:
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    return tree


def params_fn(shapes, cfg):
    """A traceable ``key -> parameters`` for a tree of shapes: float32, norm
    scales 1, biases 0, everything else normal(0, ``assumed.init_std``) cut
    from one vector drawn from the key. A leaf for which the architecture's
    file states a rule of its own (``fresh_leaf``: ``harness/arch.py``) is
    made by that rule, from the key folded with the leaf's position."""
    items = flatten(shapes)
    std = cfg["assumed"]["init_std"]
    rule = getattr(arch.of(cfg), "fresh_leaf", None)
    own = {}
    if rule is not None:
        for i, (path, shape) in enumerate(items):
            fn = rule(cfg, path, shape)
            if fn is not None:
                own[path] = (i, fn)

    def make(key):
        random = [(p, s) for p, s in items
                  if p not in own and p[-1] not in ("scale", "bias")]
        total = sum(_size(s) for _, s in random)
        flat = jax.random.normal(key, (total,), jnp.float32) * std
        out, off = {}, 0
        for path, shape in random:
            n = _size(shape)
            out[path] = flat[off:off + n].reshape(shape)
            off += n
        for path, shape in items:
            if path in own:
                i, fn = own[path]
                leaf = jnp.asarray(fn(jax.random.fold_in(key, i)),
                                   jnp.float32)
                out[path] = jnp.broadcast_to(leaf, shape)
            elif path[-1] == "scale":
                out[path] = jnp.ones(shape, jnp.float32)
            elif path[-1] == "bias":
                out[path] = jnp.zeros(shape, jnp.float32)
        return unflatten([(p, out[p]) for p, _ in items])

    return make


def make_params(shapes, seed, cfg):
    """The parameters of ``seed``, made on the device in one jitted call."""
    return jax.jit(params_fn(shapes, cfg))(key(seed))


def _size(shape):
    n = 1
    for d in shape:
        n *= d
    return n
