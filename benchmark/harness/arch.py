"""An architecture, found by name: ``benchmark/archs/<arch>.py``, where
``<arch>`` is the configuration file's ``arch``. The registry is the
directory, as ``models/`` is for a kind of model, so a new architecture is
one new file and no shared file names one.

What such a file states (it imports nothing of ``horovod_tpu``; the helpers
of ``harness/reference.py`` are there for it to import):

- ``param_shapes(cfg)``, ``fused_parts(cfg)``: the parameter tree as nested
  dicts of shape tuples under the program's names, and the leaves that fuse
  several matrices (path -> equal parts along the last axis);
- ``Net(cfg, mm)``: the network the reference steps through, with ``split``,
  ``join``, ``embed``, ``head_loss`` and its blocks by kind: ``kind_of(i)``
  (any hashable) for layer ``i`` and ``block(kind, p, x)``, which the
  reference jits once per kind;
- ``step_flops(cfg, sequences, seq_len)`` from shapes and, where the
  program runs kernels a roofline metric reads, their work in the form
  ``flops.least_seconds`` takes ({pass: {"flops", "bytes"}};
  ``flash_work(cfg, sequences, seq_len)`` for the flash kernels);
- ``REHEARSE``: the tiny sizes of its own keys for ``--rehearse`` (laid over
  the configuration's top level; a nested dict is merged into the group of
  that name);
- optionally ``fresh_leaf(cfg, path, shape)``: for a leaf that
  ``weights.params_fn``'s rule by name (``scale`` 1, ``bias`` 0, else
  normal(``init_std``)) does not serve, a traceable ``key -> float32 array``
  of that shape; None for every other leaf.
"""

import functools
import importlib.util
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.lru_cache(maxsize=None)
def load(name):
    """The module ``benchmark/archs/<name>.py``, executed once a process."""
    path = os.path.join(HERE, "archs", f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"no architecture {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(f"bench_arch_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def of(cfg):
    """The architecture's module of a configuration."""
    return load(cfg["arch"])
