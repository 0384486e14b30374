"""The measured window: a training loop that logs its loss.

Each step draws a new host batch, places it and calls the compiled step
(``program.feed``). Completion of step k is observed by fetching its loss
while steps k+1 and k+2 are already dispatched, so the observation never
starves the device and a stall of host or device shows as a long interval
between completions.
"""

import collections
import math
import time

import jax

IN_FLIGHT = 2


class Window:
    """Runs steps and keeps what the metrics need."""

    def __init__(self, feed, batches):
        self.feed, self.batches = feed, batches
        self.completions = []       # host clock at each step's completion
        self.dispatch_s = 0.0       # inside shard_batch + the step's call
        self.steps = 0
        self.bad_losses = 0
        self.opened = self.closed = None

    def _observe(self, loss):
        value = float(loss)
        self.completions.append(time.perf_counter())
        if not math.isfinite(value):
            self.bad_losses += 1

    def run(self, state, seconds=None, steps=None):
        """Drive the loop for ``seconds`` (or exactly ``steps`` steps) from
        a synced device; returns the state after the last step, synced."""
        pending = collections.deque()
        self.opened = time.perf_counter()
        while True:
            host_batch = self.batches.next()
            t0 = time.perf_counter()
            state, loss = self.feed(state, host_batch)
            self.dispatch_s += time.perf_counter() - t0
            self.steps += 1
            pending.append(loss)
            if len(pending) > IN_FLIGHT:
                self._observe(pending.popleft())
            if steps is not None:
                if self.steps >= steps:
                    break
            elif time.perf_counter() - self.opened >= seconds:
                break
        while pending:
            self._observe(pending.popleft())
        jax.block_until_ready(state)
        self.closed = time.perf_counter()
        return state

    @property
    def seconds(self):
        return self.closed - self.opened

    def intervals_ms(self):
        c = self.completions
        return [(b - a) * 1e3 for a, b in zip(c, c[1:])]


def percentile(values, q):
    """Nearest-rank percentile of ``values`` (q in 0..100)."""
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]
