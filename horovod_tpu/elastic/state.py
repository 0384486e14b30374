"""Elastic training state: commit / restore / sync.

Reference: horovod/common/elastic.py (State:29, ObjectState:127, run_fn:168)
plus the framework handlers (torch/elastic/state.py:30-255): training state is
committed in memory each epoch/step-group; on a collective failure
(``HorovodInternalError``) the last commit is restored and collectives
re-initialize; on a membership notification (``HostsUpdatedInterrupt``) the
current state is kept. ``sync()`` broadcasts rank-0's state to all ranks after
a rendezvous.

TPU adaptation: device arrays are immutable, so ``commit`` is O(1) reference
capture single-controller (no deep copy — the reference must clone mutable
torch tensors); under an hvdrun elastic launch it is a device→host snapshot
instead, because membership changes rebuild the XLA backend and device
buffers do not survive that;
``sync`` rides :func:`horovod_tpu.optim.broadcast_parameters` for pytrees and
``broadcast_object`` for python attrs. Re-initialization maps to rebuilding
the mesh from the new host set.
"""

import copy
import time

import jax.numpy as jnp

from horovod_tpu.chaos import injector as _chaos
from horovod_tpu.common import basics
from horovod_tpu.common import logging as hvd_logging
from horovod_tpu.common.exceptions import (HorovodInternalError,
                                           HostsUpdatedInterrupt)
from horovod_tpu.flight import recorder as _flight
from horovod_tpu.goodput import ledger as _goodput
from horovod_tpu.metrics import instruments as _metrics


def _elastic_launch():
    """True under an hvdrun elastic launch, where membership changes can
    rebuild the XLA backend (committed device buffers would dangle)."""
    import os
    return bool(os.environ.get("HOROVOD_ELASTIC"))


class State:
    """Base elastic state (reference: common/elastic.py:29-126)."""

    def __init__(self, **kwargs):
        self._host_messages = None  # set by the elastic worker loop
        self._reset_callbacks = []
        for k, v in kwargs.items():
            setattr(self, k, v)

    def register_reset_callbacks(self, callbacks):
        """Callbacks invoked after a reset (LR re-scaling etc.,
        reference: elastic.py:44-52)."""
        self._reset_callbacks.extend(callbacks)

    def on_reset(self):
        self.reset()
        for cb in self._reset_callbacks:
            cb()

    def commit(self):
        """Commit (save) + check for host changes (reference: elastic.py:54)."""
        t_save = time.monotonic()
        self.save()
        _goodput.note_commit(time.monotonic() - t_save)
        step = getattr(self, "step", None)
        if step is not None:
            # Step annotation BEFORE the chaos site: a crash injected at
            # this commit leaves the step marker in the victim's dump.
            # Only with a real step attribute — a step-less State must not
            # burn the auto counter the torch optimizer wrapper may be
            # driving in the same process. Not gated on _flight.armed:
            # step_marker also feeds the step profiler's ledger (its own
            # switch), and applies the flight gate itself.
            _flight.step_marker(step)
        if _chaos.armed:
            # Chaos site: the step boundary — where a worker crash/hang is
            # injected (the committed step also advances the plan's step
            # clock, so KV/dispatch faults can be step-keyed).
            _chaos.fire("elastic.commit", step=step)
        self.check_host_updates()

    def save(self):
        raise NotImplementedError

    def restore(self):
        raise NotImplementedError

    def sync(self):
        raise NotImplementedError

    def detach_to_host(self):
        """Pull live device-array attrs to host memory. Called by the
        elastic re-init path BEFORE the XLA backend teardown: on the
        skip_sync (removal-only) path the CURRENT attrs survive into the
        new backend, and buffers of the destroyed PJRT client must not
        leak into post-re-init computation (committed state is already
        host-side under an elastic launch, save()). Default: no-op."""

    def reset(self):
        pass

    def check_host_updates(self):
        """Raise HostsUpdatedInterrupt when the driver published a new host
        set (reference: elastic.py:75-100 via WorkerNotificationManager; here
        a KV version poll)."""
        if self._host_messages is None:
            return
        observed = self._host_messages.poll()
        if observed is not None:
            # Removal-only update windows skip the re-sync: survivors
            # keep their CURRENT (possibly uncommitted) attrs, matching
            # the reference's HostUpdateResult.removed -> skip_sync path
            # (common/elastic.py). Additions must sync so new workers
            # receive rank 0's state. Decided BEFORE acknowledge(): the
            # kind walk spans (last-acknowledged, observed] and its KV
            # reads are fallible — an error after acknowledging would
            # swallow the interrupt for good.
            skip = self._host_messages.removal_only(observed)
            # Acknowledge exactly the observed version before raising so
            # the next commit after recovery doesn't re-trigger on it — a
            # bump published in between must still raise later.
            self._host_messages.acknowledge(observed)
            raise HostsUpdatedInterrupt(skip_sync=skip)


class ObjectState(State):
    """State of arbitrary python attributes, synced by object broadcast
    (reference: common/elastic.py:127-170)."""

    def __init__(self, bcast_object=None, **kwargs):
        from horovod_tpu.ops.collective_ops import broadcast_object
        self._bcast_object = bcast_object or broadcast_object
        self._saved_state = dict(kwargs)
        super().__init__(**kwargs)

    def save(self):
        import jax

        def _snap(x):
            # jax arrays: immutable, but NOT donation-proof — a reference
            # would alias a buffer that make_train_step(donate=True)
            # invalidates on the next step, so snapshot to a fresh device
            # buffer (host memory under an elastic launch, where membership
            # changes tear the whole backend down). Anything else (torch
            # tensors, python objects) keeps deepcopy semantics;
            # device_get must never touch those — __array__ coercion would
            # silently hand back numpy (or raise on device tensors).
            if isinstance(x, jax.Array):
                return jax.device_get(x) if _elastic_launch() \
                    else jnp.array(x, copy=True)
            return copy.deepcopy(x)

        self._saved_state = {
            attr: jax.tree_util.tree_map(_snap, getattr(self, attr))
            for attr in self._saved_state.keys()}

    def restore(self):
        for attr, value in self._saved_state.items():
            setattr(self, attr, copy.deepcopy(value))

    def sync(self):
        if self._saved_state:
            synced = self._bcast_object(self._saved_state, root_rank=0)
            for attr, value in synced.items():
                setattr(self, attr, value)
            self._saved_state = synced

    def detach_to_host(self):
        import jax

        def conv(x):
            return jax.device_get(x) if isinstance(x, jax.Array) else x

        for attr in self._saved_state:
            setattr(self, attr,
                    jax.tree_util.tree_map(conv, getattr(self, attr)))


class TpuState(ObjectState):
    """Model/optimizer state for JAX training loops.

    Tracked pytrees (``params``, ``opt_state``, anything passed as a pytree
    kwarg) are committed as fresh device copies (immutability alone is not
    enough — donated train steps invalidate the old buffers) and synced with
    a fused broadcast — the analog of TorchState(model=..., optimizer=...)
    (reference: torch/elastic/state.py).
    """

    def __init__(self, trees=None, **kwargs):
        self._trees = dict(trees or {})
        self._saved_trees = dict(self._trees)
        super().__init__(**kwargs)

    def __getattr__(self, name):
        trees = self.__dict__.get("_trees", {})
        if name in trees:
            return trees[name]
        raise AttributeError(name)

    def __setattr__(self, name, value):
        if not name.startswith("_") and "_trees" in self.__dict__ \
                and name in self._trees:
            self._trees[name] = value
        else:
            super().__setattr__(name, value)

    def save(self):
        # Immutable jax arrays still need a REAL copy: a reference would
        # alias buffers make_train_step(donate=True) invalidates on the
        # next step. Under an elastic launch the snapshot must additionally
        # survive a backend teardown on membership change (reference
        # semantics: torch handlers clone to a safe copy,
        # torch/elastic/state.py:154+), so it goes to host memory there.
        import jax

        if _elastic_launch():
            self._saved_trees = jax.device_get(dict(self._trees))
        else:
            self._saved_trees = jax.tree_util.tree_map(
                lambda x: jnp.array(x, copy=True)
                if isinstance(x, jax.Array) else copy.deepcopy(x),
                dict(self._trees))
        super().save()

    def restore(self):
        self._trees = dict(self._saved_trees)
        super().restore()

    def sync(self):
        from horovod_tpu.optim import broadcast_parameters
        for name, tree in self._trees.items():
            self._trees[name] = broadcast_parameters(tree, root_rank=0)
        super().sync()

    def detach_to_host(self):
        import jax

        def conv(x):
            return jax.device_get(x) if isinstance(x, jax.Array) else x

        self._trees = {name: jax.tree_util.tree_map(conv, tree)
                       for name, tree in self._trees.items()}
        super().detach_to_host()


def run(func):
    """Elastic run decorator (reference: common/elastic.py:168 run_fn).

    ``@hvd.elastic.run`` wraps ``train(state, ...)``: syncs state on entry,
    retries on ``HorovodInternalError`` (restore last commit) and
    ``HostsUpdatedInterrupt`` (keep state), re-initializing between attempts.
    """

    def wrapper(state, *args, **kwargs):
        from horovod_tpu.elastic.worker import (arm_collective_abort,
                                                configured_version,
                                                disarm_collective_abort,
                                                mark_new_rank_ready,
                                                read_new_rank_ready,
                                                wait_for_version_change)
        reset_required = False
        skip_sync = False
        # (cause, monotonic detection time) of the oldest unrecovered
        # failure: observed into elastic_recovery_seconds when training
        # re-enters — the detection → first-post-restore-step latency the
        # soak harness (and capacity planning) cares about. Not reset by
        # a second interrupt landing mid-recovery: the user-visible outage
        # runs from the FIRST detection.
        recovering = None
        while True:
            known_version = configured_version()
            try:
                if reset_required:
                    _reset(state)
                    reset_required = False
                # Fork-parity scale-up barrier: announce this worker and
                # wait until the whole membership is up before the state
                # broadcast (reference: horovod_mark_new_rank_ready
                # handshake, operations.cc:1264-1305). Raises
                # HostsUpdatedInterrupt if membership moves while waiting.
                # No-op outside elastic launches.
                mark_new_rank_ready()
                read_new_rank_ready()
                if _sync_vote(want_sync=not skip_sync):
                    _metrics.record_elastic_event("sync")
                    state.sync()
                skip_sync = False
                known_version = configured_version()
                if recovering is not None:
                    _goodput.note_recovery(
                        recovering[0], time.monotonic() - recovering[1])
                    _metrics.record_elastic_recovery(
                        recovering[0], time.monotonic() - recovering[1])
                    recovering = None
                # Membership watchdog: while the user function runs, a
                # published removal severs in-flight collectives so EVERY
                # rank (not just the dead peer's gloo neighbors) fails
                # fast into the except arms below. Disarmed on unwind —
                # the recovery path's fresh rendezvous sockets must not
                # be severed by a stale observation.
                arm_collective_abort(known_version)
                try:
                    return func(state, *args, **kwargs)
                finally:
                    disarm_collective_abort()
            except HorovodInternalError:
                if recovering is None:
                    recovering = ("failure", time.monotonic())
                # Goodput phase flip: everything from here to the first
                # post-restore step boundary (including the destroyed
                # open window) is rendezvous_recovery badput.
                _goodput.note_reset()
                _metrics.record_elastic_event("restore")
                # The ring's tail at this moment is the failed collective
                # plus everything leading up to it — dump before restore
                # overwrites any of it with recovery traffic.
                _flight.dump("horovod_internal_error")
                hvd_logging.warning(
                    "collective failure; restoring last committed state")
                state.restore()
                # A peer likely died: give the driver time to notice and
                # publish a shrunk membership before re-rendezvous, else we
                # would re-init at the old world size and block on the dead
                # rank (reference: driver notices the exit and republishes,
                # elastic/driver.py:304+; workers loop on re-rendezvous).
                wait_for_version_change(known_version)
                reset_required = True
            except HostsUpdatedInterrupt as e:
                if recovering is None:
                    recovering = ("host_update", time.monotonic())
                _goodput.note_reset()
                _metrics.record_elastic_event("host_update")
                hvd_logging.info("host set updated; re-initializing")
                reset_required = True
                skip_sync = e.skip_sync

    def _sync_vote(want_sync):
        """COLLECTIVE sync decision: sync iff ANY member of the (new)
        membership needs it. Members can legitimately disagree locally —
        a new worker or a HorovodInternalError-recoverer needs the rank-0
        broadcast, while a graceful removal-only survivor does not — and
        ``sync()`` is a collective, so acting on divergent local flags
        would hang the broadcast with mismatched participants. One tiny
        KV exchange makes the decision unanimous (the reference gets this
        consistency from its push NotificationService delivering the same
        update to every worker). Outside elastic multi-process launches:
        the local flag decides, as before."""
        import jax

        if not _elastic_launch() or jax.process_count() <= 1:
            return want_sync
        from horovod_tpu.common import negotiation
        votes = negotiation.exchange("elastic_sync_vote", bool(want_sync))
        return any(votes)

    def _reset(state):
        """In-place re-initialization at the current membership: surviving
        workers keep their process (and committed state) and rebuild the
        collective runtime — the reference's shutdown → re-rendezvous →
        re-init sequence (common/elastic.py:168 run_fn + §3.4 call stack)."""
        import os

        from horovod_tpu.elastic.worker import refresh_assignment_env
        _metrics.record_elastic_event("reset")
        # Live attrs must not carry buffers of the client we are about to
        # destroy into the new backend (the skip_sync path keeps them).
        try:
            state.detach_to_host()
        except NotImplementedError:
            pass
        basics.shutdown()
        consumed_version = refresh_assignment_env()
        if consumed_version is None:
            hvd_logging.info(
                "host removed from membership; exiting cleanly")
            # Last words before SystemExit below.
            _flight.dump("membership_removed")
            # Orderly disconnect before dying: letting interpreter
            # finalization destroy the jax.distributed client (and, on a
            # coordinator, the service with peers still attached) can
            # fire the hardwired fatal callback on us or on survivors.
            basics.teardown_distributed()
            raise SystemExit(0)
        if os.environ.get("HOROVOD_ELASTIC") and \
                basics._distributed_client_active():
            # Tear the old cluster down fully: the coordinator/port and the
            # world size may both have changed, and device buffers from the
            # old backend are invalid in the new one (commits are host-side
            # snapshots for exactly this reason).
            basics.teardown_distributed()
        basics.init()
        if getattr(state, "_host_messages", None) is not None:
            # Acknowledge exactly the version this re-init consumed: a bump
            # published since must still raise at the next commit.
            state._host_messages.acknowledge(consumed_version)
        state.on_reset()

    return wrapper
