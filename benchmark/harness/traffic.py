"""The one general generator of training batches.

A cell's file gives sequences per chip and sequence length, its
configuration's ``inputs`` gives the integer fields of a batch (per token
or per sequence, and the exclusive upper end of their values). Every step
gets a new batch, drawn on the host from the seed; every seed gives the
same shapes.
"""

import numpy as np


class Batches:
    """Host batches of one cell, in order, from ``seed``."""

    def __init__(self, cfg, workload, seed):
        self.fields = cfg["inputs"]
        self.rows = workload["sequences_per_chip"] * workload["chips"]
        self.seq_len = workload["sequence_length"]
        self._rng = np.random.default_rng([int(seed), 0x62617463])

    def next(self):
        batch = {}
        for name, spec in self.fields.items():
            shape = ((self.rows, self.seq_len) if spec["per"] == "token"
                     else (self.rows,))
            batch[name] = self._rng.integers(0, spec["high"], shape,
                                             dtype=np.int32)
        return batch

    @property
    def tokens_per_step(self):
        return self.rows * self.seq_len
