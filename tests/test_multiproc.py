"""Tier-2 harness: collectives across REAL process boundaries.

The reference runs every parallel test under ``horovodrun -np 2 -H
localhost:2`` so N OS processes exercise the full negotiation/collective
stack (reference: .buildkite/gen-pipeline.sh:126-149, test/parallel/
test_torch.py dtype/op sweeps). This file is the analog: ``run()`` spawns
real ``jax.distributed`` CPU processes on loopback "hosts", each owning its
slots' virtual devices, and the collective battery asserts every eager op
against numpy — including the dynamic-shape paths that require host-side
size negotiation (ragged allgather, uneven alltoall).
"""

import sys

import cloudpickle
import numpy as np
import pytest

from horovod_tpu.runner import run

# The dominant 2-process x 2-chip topology rides ONE persistent cluster
# (see tests/cluster.py + the shared_cluster fixture): each test dispatches
# its worker fn to the live, already-bootstrapped processes.
H22 = "localhost:2,127.0.0.1:2"

# Worker processes can't import this test module by name; ship the battery
# functions by value instead.
cloudpickle.register_pickle_by_value(sys.modules[__name__])


def _battery(tag):
    """Runs inside each spawned worker process. Exercises every eager
    collective and checks the math against numpy; any failure raises and
    fails the launch."""
    import numpy as np
    import horovod_tpu as hvd

    n = hvd.size()
    topo = hvd.topology()
    lr = topo.local_device_ranks       # global ranks owned by this process
    nl = len(lr)
    passed = []

    def rows(fn):
        """Local rank-major stack from a per-global-rank row function."""
        return np.stack([fn(r) for r in lr]).astype(np.float32)

    def world(fn):
        return np.stack([fn(r) for r in range(n)]).astype(np.float32)

    base = np.arange(3, dtype=np.float32)

    # --- allreduce: Sum / Average / Min / Max ---
    local = rows(lambda r: base + r)
    full = world(lambda r: base + r)
    for op, red in ((hvd.Sum, full.sum(0)), (hvd.Average, full.mean(0)),
                    (hvd.Min, full.min(0)), (hvd.Max, full.max(0))):
        out = np.asarray(hvd.allreduce(local, op=op))
        np.testing.assert_allclose(
            out, np.broadcast_to(red, (nl, 3)), rtol=1e-5)
    passed.append("allreduce")

    # --- grouped allreduce with pre/postscale ---
    outs = hvd.grouped_allreduce([local, local * 2], op=hvd.Sum,
                                 prescale_factor=0.5, postscale_factor=2.0)
    np.testing.assert_allclose(np.asarray(outs[0]),
                               np.broadcast_to(full.sum(0), (nl, 3)),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(outs[1]),
                               np.broadcast_to(2 * full.sum(0), (nl, 3)),
                               rtol=1e-5)
    passed.append("grouped_allreduce")

    # --- broadcast from a non-zero root ---
    out = np.asarray(hvd.broadcast(local, root_rank=1))
    np.testing.assert_allclose(out, np.broadcast_to(base + 1, (nl, 3)),
                               rtol=1e-5)
    passed.append("broadcast")

    # --- allgather ---
    loc2 = rows(lambda r: np.array([r, r + 0.5]))
    out = np.asarray(hvd.allgather(loc2))     # (nl, 2n)
    expect = world(lambda r: np.array([r, r + 0.5])).reshape(-1)
    np.testing.assert_allclose(out, np.broadcast_to(expect, (nl, 2 * n)),
                               rtol=1e-5)
    passed.append("allgather")

    # --- ragged allgather (negotiated first dims) ---
    ragged_local = [np.full((r + 1, 2), float(r), np.float32) for r in lr]
    out = np.asarray(hvd.allgather_ragged(ragged_local))
    expect = np.concatenate(
        [np.full((r + 1, 2), float(r), np.float32) for r in range(n)])
    np.testing.assert_allclose(out, expect, rtol=1e-5)
    passed.append("allgather_ragged")

    # --- hierarchical allgather (cross_size > 1 here: the rank-ordering
    # property rank = cross*local_size + local is actually exercised,
    # unlike the single-process CPU tier where cross=1) ---
    from horovod_tpu.common import basics as _basics
    cfg = _basics.config()
    cfg.hierarchical_allgather = True
    try:
        out = np.asarray(hvd.allgather(loc2))
    finally:
        cfg.hierarchical_allgather = False
    expect_h = world(lambda r: np.array([r, r + 0.5])).reshape(-1)
    np.testing.assert_allclose(out, np.broadcast_to(expect_h, (nl, 2 * n)),
                               rtol=1e-5)
    passed.append("allgather_hier")

    # --- reducescatter ---
    rs_in = rows(lambda r: np.arange(2 * n) + r)   # (nl, 2n)
    out = np.asarray(hvd.reducescatter(rs_in, op=hvd.Sum))  # (nl, 2)
    full_rs = world(lambda r: np.arange(2 * n) + r)
    for i, r in enumerate(lr):
        np.testing.assert_allclose(out[i], full_rs.sum(0)[2 * r:2 * r + 2],
                                   rtol=1e-5)
    passed.append("reducescatter")

    # --- alltoall, even splits ---
    a2a_in = rows(lambda r: 10.0 * r + np.arange(n))    # (nl, n)
    out = np.asarray(hvd.alltoall(a2a_in))              # (nl, n)
    for i, r in enumerate(lr):
        np.testing.assert_allclose(out[i],
                                   np.array([10.0 * p + r for p in range(n)]),
                                   rtol=1e-5)
    passed.append("alltoall")

    # --- alltoall, uneven splits (negotiated) ---
    full_splits = np.array([[(r + p) % 2 + 1 for p in range(n)]
                            for r in range(n)])
    m = int(full_splits.sum(axis=1).max())
    send = np.stack([np.pad(100.0 * r + np.arange(full_splits[r].sum()),
                            (0, m - full_splits[r].sum()))
                     for r in lr]).astype(np.float32)
    multi = hvd.process_count() > 1
    splits_arg = full_splits[lr] if multi else full_splits
    got_rows, received = hvd.alltoall(send, splits=splits_arg)
    offs = np.concatenate([np.zeros((n, 1), int),
                           np.cumsum(full_splits, axis=1)], axis=1)
    for i, r in enumerate(lr):
        expect = np.concatenate([
            100.0 * p + np.arange(offs[p, r], offs[p, r + 1])
            for p in range(n)]).astype(np.float32)
        np.testing.assert_allclose(np.asarray(got_rows[i]), expect, rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(received[i]),
                                      full_splits[:, r])
    passed.append("alltoall_uneven")

    # --- async allreduce through the fusion runtime ---
    h1 = hvd.allreduce_async(local, op=hvd.Sum)
    h2 = hvd.allreduce_async(local * 3.0, op=hvd.Sum)
    np.testing.assert_allclose(np.asarray(h1.synchronize()),
                               np.broadcast_to(full.sum(0), (nl, 3)),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h2.synchronize()),
                               np.broadcast_to(3 * full.sum(0), (nl, 3)),
                               rtol=1e-5)
    passed.append("allreduce_async")

    # --- object collectives (pickled, size-negotiated) ---
    got = hvd.broadcast_object({"from": "proc0", "x": 7}, root_rank=0)
    assert got == {"from": "proc0", "x": 7}, got
    objs = hvd.allgather_object([("obj", r, "payload" * (r + 1))
                                 for r in lr])
    assert objs == [("obj", r, "payload" * (r + 1)) for r in range(n)], objs
    passed.append("object_collectives")

    # --- barrier ---
    hvd.barrier()
    passed.append("barrier")

    return (tag, hvd.rank(), n, hvd.process_count(), passed)


ALL_OPS = ["allreduce", "grouped_allreduce", "broadcast", "allgather",
           "allgather_ragged", "allgather_hier", "reducescatter", "alltoall",
           "alltoall_uneven", "allreduce_async", "object_collectives",
           "barrier"]


class TestMultiProcessCollectives:
    def test_two_processes_two_slots_each(self, shared_cluster):
        """2 processes x 2 chips: every collective crosses the boundary."""
        results = shared_cluster(H22).run(_battery, args=("t2",))
        assert len(results) == 2
        for (tag, rank, n, pc, passed), want_rank in zip(results, (0, 2)):
            assert (tag, rank, n, pc) == ("t2", want_rank, 4, 2)
            assert passed == ALL_OPS

    def test_four_processes(self, shared_cluster):
        """4 single-slot processes on loopback aliases (the reference's
        -np 4 tier)."""
        results = shared_cluster(
            "localhost:1,127.0.0.1:1,127.0.0.2:1,127.0.0.3:1").run(
                _battery, args=("t4",))
        assert len(results) == 4
        for (tag, rank, n, pc, passed), want_rank in zip(results, range(4)):
            assert (tag, rank, n, pc) == ("t4", want_rank, 4, 4)
            assert passed == ALL_OPS


class TestMultiProcessSemantics:
    def test_join_raises_multiprocess(self):
        def fn():
            import horovod_tpu as hvd
            # NotImplementedError, NOT HorovodInternalError: the elastic
            # @run wrapper retries the latter, so a deterministic usage
            # error must use a non-retryable type.
            try:
                hvd.join()
            except NotImplementedError:
                return "raised"
            return "no-error"

        results = run(fn, hosts="localhost:1,127.0.0.1:1")
        assert results == ["raised", "raised"]


def _checkpoint_worker(ckpt_dir):
    """Sharded checkpoint save/restore ACROSS real process boundaries:
    every process holds only its shards of a dp-sharded train state; the
    orbax-backed manager must write one coherent checkpoint and restore
    it onto the same multi-process mesh (SURVEY §5.4; the reference's
    elastic resume crosses hosts the same way)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.checkpoint import CheckpointManager

    mesh = hvd.global_process_set.mesh
    n = hvd.size()
    sharded = NamedSharding(mesh, P("hvd"))
    # deterministic global value, dp-sharded: every process supplies its
    # local rows only
    lr = hvd.topology().local_device_ranks
    local = np.stack([np.arange(4.0, dtype=np.float32) + r for r in lr])
    moments = jax.make_array_from_process_local_data(sharded, local,
                                                     (n, 4))
    state = {"step": jnp.asarray(7), "moments": moments}
    mngr = CheckpointManager(ckpt_dir, max_to_keep=2)
    mngr.save(7, state, wait=True)

    template = {"step": jnp.zeros((), jnp.int32),
                "moments": jax.ShapeDtypeStruct((n, 4), jnp.float32,
                                                sharding=sharded)}
    out = mngr.restore(template=template)
    mngr.close()
    assert int(out["step"]) == 7
    got = out["moments"]
    assert got.sharding.is_equivalent_to(sharded, 2)
    # each process verifies ITS addressable shards round-tripped exactly
    for shard in got.addressable_shards:
        r = shard.index[0].start or 0
        np.testing.assert_array_equal(
            np.asarray(shard.data)[0], np.arange(4.0) + r)
    return "ok"


def _timeline_worker(tl_dir):
    """Per-process timeline paths under a multi-process launch: the
    coordinator writes the configured file, others suffix .p<index> —
    no clobbering one shared file (reference: rank-0 timeline writer)."""
    import os

    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.common import basics

    path = os.path.join(tl_dir, "t.json")
    basics.start_timeline(path)
    hvd.allreduce(np.ones((len(hvd.topology().local_device_ranks), 2),
                          np.float32))
    basics.stop_timeline()
    expect = path if hvd.process_index() == 0 \
        else f"{path}.p{hvd.process_index()}"
    assert os.path.exists(expect), expect
    return os.path.basename(expect)


def _checkpoint_mismatch_worker(ckpt_dir):
    """A host-local leaf that DIFFERS across processes (a rank-folded
    PRNG key, a local metric) must fail the save loudly — silently
    stamping the primary's value would corrupt resumes."""
    import jax.numpy as jnp

    import horovod_tpu as hvd
    from horovod_tpu.checkpoint import CheckpointManager

    mngr = CheckpointManager(ckpt_dir)
    try:
        mngr.save(1, {"local": jnp.asarray(float(hvd.process_index()))},
                  wait=True)
        return "no-error"
    except ValueError as e:
        assert "differ between" in str(e), e
        return "caught"


class TestMultiProcessCheckpoint:
    def test_sharded_save_restore_crosses_processes(self, shared_cluster,
                                                    tmp_path):
        c = shared_cluster(H22)
        results = c.run(_checkpoint_worker, args=(str(tmp_path),))
        assert results == ["ok", "ok"]

    def test_per_process_leaf_fails_loudly(self, shared_cluster, tmp_path):
        c = shared_cluster(H22)
        results = c.run(_checkpoint_mismatch_worker,
                        args=(str(tmp_path / "bad"),))
        assert results == ["caught", "caught"]

    def test_timeline_per_process_paths(self, shared_cluster, tmp_path):
        c = shared_cluster(H22)
        results = c.run(_timeline_worker, args=(str(tmp_path),))
        assert results == ["t.json", "t.json.p1"]


def _async_cycle_worker():
    """Sub-threshold async enqueue with NO synchronize/poll: the
    coordinator's cycle thread must flush it and every follower must apply
    the published boundary in the background (VERDICT round-2 item 5 —
    reduction/backward overlap for torch-hook training on multi-host)."""
    import time

    import numpy as np

    import horovod_tpu as hvd

    n = hvd.size()
    nl = len(hvd.topology().local_device_ranks)
    h = hvd.allreduce_async(
        np.ones((nl, 4), np.float32) * (hvd.rank() + 1), op=hvd.Sum,
        name="cycle_probe")
    deadline = time.time() + 30
    while time.time() < deadline and h._result is None and h._error is None:
        time.sleep(0.05)
    assert h._error is None, h._error
    assert h._result is not None, "background cycle flush never happened"
    out = np.asarray(h.synchronize())
    want = float(sum(r + 1 for r in range(n)))
    np.testing.assert_allclose(out, np.full((nl, 4), want), rtol=1e-5)
    return "ok"


def _int8_wire_worker():
    """Async fused allreduce under HOROVOD_WIRE_DTYPE=int8 at world 4
    (2 procs x 2 chips): the big bucket rides the quantized exchange
    (error bounded but nonzero), the small one stays exact."""
    import numpy as np

    import horovod_tpu as hvd

    n = hvd.size()
    nl = len(hvd.topology().local_device_ranks)
    rng = np.random.default_rng(7)
    # per-device shard must clear the n*1024 inflation guard
    big_all = rng.standard_normal((n, 8192)).astype(np.float32)
    lr = hvd.topology().local_device_ranks
    big = big_all[lr]
    h = hvd.allreduce_async(big, op=hvd.Sum, name="int8big")
    out = np.asarray(h.synchronize())
    want = big_all.sum(0)
    err = np.abs(out[0] - want).max()
    bound = 4 * np.abs(big_all).max() * n / 127
    assert 0 < err < bound, (err, bound)
    small = np.ones((nl, 8), np.float32)
    hs = hvd.allreduce_async(small, op=hvd.Sum, name="int8small")
    np.testing.assert_allclose(np.asarray(hs.synchronize()),
                               np.full((nl, 8), float(n)), rtol=1e-5)
    return "ok"


def _async_sync_interleave_worker():
    """Sync eager collectives interleaved with in-flight async enqueues:
    the sync-op fence must keep the device-collective submission order
    identical on every process (coordinator: flush-then-sync; followers:
    apply-boundary-then-sync) — without it the orders can invert on a
    lagging follower and the job hangs or corrupts."""
    import time

    import numpy as np

    import horovod_tpu as hvd

    n = hvd.size()
    nl = len(hvd.topology().local_device_ranks)
    handles = []
    for i in range(40):
        h = hvd.allreduce_async(np.full((nl, 4), float(i), np.float32),
                                op=hvd.Sum, name=f"s{i}")
        handles.append((i, h))
        if i % 9 == 4:
            time.sleep(0.004)       # let the coordinator's cycle fire
        if i % 11 == 6:
            # a SYNC collective lands mid-stream (the hazard case)
            out = np.asarray(hvd.allreduce(np.ones((nl, 2), np.float32),
                                           op=hvd.Sum))
            np.testing.assert_allclose(out, np.full((nl, 2), float(n)),
                                       rtol=1e-5)
    for i, h in handles:
        np.testing.assert_allclose(np.asarray(h.synchronize()),
                                   np.full((nl, 4), i * n), rtol=1e-5)
    return "ok"


class TestMultiProcessAsyncCycle:
    def test_subthreshold_flush_without_synchronize_world4(self,
                                                           shared_cluster):
        c = shared_cluster("localhost:1,127.0.0.1:1,127.0.0.2:1,"
                           "127.0.0.3:1")
        assert c.run(_async_cycle_worker) == ["ok"] * 4

    def test_sync_interleaved_with_async_world4(self, shared_cluster):
        c = shared_cluster("localhost:1,127.0.0.1:1,127.0.0.2:1,"
                           "127.0.0.3:1")
        assert c.run(_async_sync_interleave_worker) == ["ok"] * 4

    def test_sync_interleaved_with_async_2x2(self, shared_cluster):
        assert shared_cluster(H22).run(
            _async_sync_interleave_worker) == ["ok", "ok"]

    def test_int8_wire_async_2x2(self, shared_cluster):
        """HOROVOD_WIRE_DTYPE=int8 across real processes: the int8 wire
        name must survive the coordinator->follower boundary publish and
        the quantized fused program must agree on both processes."""
        c = shared_cluster(H22, extra_env={"HOROVOD_WIRE_DTYPE": "int8"})
        assert c.run(_int8_wire_worker) == ["ok", "ok"]


def _join_worker():
    """Reference JOIN semantics across real process boundaries
    (controller.cc:269-327): processes 1 and 3 run out of data and join
    early; 0 and 2 keep issuing collectives whose results must exclude the
    joined ranks exactly; then everyone joins, state resets, and a final
    full-world collective works."""
    import numpy as np
    import horovod_tpu as hvd

    n = hvd.size()
    r = hvd.rank()
    base = np.arange(3, dtype=np.float32)
    local = (base + r)[None].astype(np.float32)     # local stack: 1 chip
    full = np.stack([base + i for i in range(n)])

    # everyone active: ordinary full-world collective (pays the armed-mode
    # round, result unchanged)
    out = np.asarray(hvd.allreduce(local, op=hvd.Average))
    np.testing.assert_allclose(out, np.broadcast_to(full.mean(0), (1, 3)),
                               rtol=1e-5)

    if r in (1, 3):
        last = hvd.join()            # services the actives' collectives
    else:
        act = [0, 2]
        full_act = np.stack([base + i for i in act])
        checks = [
            (hvd.Sum, full_act.sum(0)),
            (hvd.Average, full_act.mean(0)),
            (hvd.Min, full_act.min(0)),
            (hvd.Max, full_act.max(0)),
        ]
        for op, want in checks:
            out = np.asarray(hvd.allreduce(local, op=op))
            np.testing.assert_allclose(
                out, np.broadcast_to(want, (1, 3)), rtol=1e-5,
                err_msg=f"op={op}")
        # allgather drops the joined ranks' slices
        out = np.asarray(hvd.allgather(local))
        np.testing.assert_allclose(
            out, np.broadcast_to(full_act.reshape(-1), (1, 2 * 3)),
            rtol=1e-5)
        # ragged allgather: joined ranks contribute zero rows
        ragged = [np.full((r // 2 + 1, 2), float(r), np.float32)]
        out = np.asarray(hvd.allgather_ragged(ragged))
        expect = np.concatenate(
            [np.full((i // 2 + 1, 2), float(i), np.float32) for i in act])
        np.testing.assert_allclose(out, expect, rtol=1e-5)
        # broadcast from an active root
        out = np.asarray(hvd.broadcast(local, root_rank=2))
        np.testing.assert_allclose(out, np.broadcast_to(base + 2, (1, 3)),
                                   rtol=1e-5)
        # async rides the sync bypass while armed (fusion can't open the
        # join round at enqueue time) — and still masks the joined ranks
        h = hvd.allreduce_async(local, op=hvd.Sum, name="armed")
        np.testing.assert_allclose(
            np.asarray(h.synchronize()),
            np.broadcast_to(full_act.sum(0), (1, 3)), rtol=1e-5)
        last = hvd.join()
    # Everyone returns the last round's highest newly-joined rank, and the
    # join state has reset: a full-world collective works again.
    out = np.asarray(hvd.allreduce(local, op=hvd.Sum))
    np.testing.assert_allclose(out, np.broadcast_to(full.sum(0), (1, 3)),
                               rtol=1e-5)
    # SECOND join cycle with the roles swapped: the protocol (and its
    # round counters) must be reusable after a completed join.
    if r in (0, 2):
        last2 = hvd.join()
    else:
        act2 = [1, 3]
        full_act2 = np.stack([base + i for i in act2])
        out = np.asarray(hvd.allreduce(local, op=hvd.Average))
        np.testing.assert_allclose(
            out, np.broadcast_to(full_act2.mean(0), (1, 3)), rtol=1e-5)
        last2 = hvd.join()
    out = np.asarray(hvd.allreduce(local, op=hvd.Sum))
    np.testing.assert_allclose(out, np.broadcast_to(full.sum(0), (1, 3)),
                               rtol=1e-5)
    return (r, last, last2)


def _join_subset_worker():
    """Set-scoped JOIN (reference: joined_size is per ProcessSet,
    controller.cc:269-327): rank 1 joins INSIDE the 2-rank subset {0,1}
    while processes 2,3 keep training on their own subset {2,3} —
    completely untouched by the join protocol (set rounds are scoped to
    the set's owner processes). Then the roles inside {0,1} swap to prove
    the set protocol resets and is reusable."""
    import numpy as np
    import horovod_tpu as hvd

    r = hvd.rank()
    base = np.arange(3, dtype=np.float32)
    local = (base + r)[None].astype(np.float32)     # local stack: 1 chip
    full = np.stack([base + i for i in range(hvd.size())])

    set_a = hvd.add_process_set(hvd.ProcessSet([0, 1]))
    set_b = hvd.add_process_set(hvd.ProcessSet([2, 3]))
    try:
        last = last2 = None
        if r == 1:
            last = hvd.join(process_set=set_a)  # services A-scoped mirrors
        elif r == 0:
            # rank 1 joined: every A-scoped collective masks it out
            for op, want in ((hvd.Sum, base), (hvd.Average, base)):
                out = np.asarray(hvd.allreduce(local, op=op,
                                               process_set=set_a))
                np.testing.assert_allclose(
                    out, np.broadcast_to(want, (1, 3)), rtol=1e-5,
                    err_msg=f"op={op}")
            out = np.asarray(hvd.allgather(local, process_set=set_a))
            np.testing.assert_allclose(out, np.broadcast_to(base, (1, 3)),
                                       rtol=1e-5)
            out = np.asarray(hvd.allgather_ragged(
                [np.full((2, 2), 7.0, np.float32)], process_set=set_a))
            np.testing.assert_allclose(out, np.full((2, 2), 7.0), rtol=1e-5)
            out = np.asarray(hvd.broadcast(local, root_rank=0,
                                           process_set=set_a))
            np.testing.assert_allclose(out, np.broadcast_to(base, (1, 3)),
                                       rtol=1e-5)
            last = hvd.join(process_set=set_a)
        else:
            # THE COMPLEMENT KEEPS TRAINING: B-scoped collectives run
            # while {0,1} is mid-join — if set rounds wrongly rode the
            # global tag these would deadlock (rank 1 only answers A's).
            full_b = np.stack([base + i for i in (2, 3)])
            for _ in range(4):
                out = np.asarray(hvd.allreduce(local, op=hvd.Sum,
                                               process_set=set_b))
                np.testing.assert_allclose(
                    out, np.broadcast_to(full_b.sum(0), (1, 3)), rtol=1e-5)
        # Cycle 2, roles swapped inside A: the set's protocol state and
        # round counters must be reusable after a completed set join.
        if r == 0:
            last2 = hvd.join(process_set=set_a)
        elif r == 1:
            out = np.asarray(hvd.allreduce(local, op=hvd.Sum,
                                           process_set=set_a))
            np.testing.assert_allclose(out, np.broadcast_to(base + 1, (1, 3)),
                                       rtol=1e-5)
            last2 = hvd.join(process_set=set_a)
        # Full-world sanity: the global set never saw a join; everyone
        # meets again on one armed global round.
        out = np.asarray(hvd.allreduce(local, op=hvd.Sum))
        np.testing.assert_allclose(out, np.broadcast_to(full.sum(0), (1, 3)),
                                   rtol=1e-5)
    finally:
        hvd.remove_process_set(set_a)
        hvd.remove_process_set(set_b)
    return (r, last, last2)


class TestMultiProcessJoin:
    def test_join_world4(self):
        """VERDICT round-2 item 3: Sum/Average/Min/Max/allgather/ragged/
        broadcast with joined ranks on OTHER processes, world 4."""
        results = run(_join_worker,
                      hosts="localhost:1,127.0.0.1:1,127.0.0.2:1,"
                            "127.0.0.3:1",
                      extra_env={"HOROVOD_JOIN_MODE": "1"})
        # cycle 1: ranks 0 and 2 joined together in the final round ->
        # last = 2; cycle 2 (roles swapped): ranks 1 and 3 -> last = 3
        assert sorted(results) == [(0, 2, 3), (1, 2, 3), (2, 2, 3),
                                   (3, 2, 3)]

    def test_join_subset_world4(self):
        """VERDICT round-3 item 5: joining a rank inside a 2-rank subset
        while the complement keeps training on its own subset."""
        results = run(_join_subset_worker,
                      hosts="localhost:1,127.0.0.1:1,127.0.0.2:1,"
                            "127.0.0.3:1",
                      extra_env={"HOROVOD_JOIN_MODE": "1"})
        # cycle 1: rank 0 is the last joiner of set A -> 0; cycle 2
        # (swapped): rank 1 -> 1. The complement (2,3) never joins.
        assert sorted(results) == [(0, 0, 1), (1, 0, 1), (2, None, None),
                                   (3, None, None)]


class TestMultiProcessWorldEight:
    def test_two_processes_four_slots_each(self):
        """n=8 world across a real process boundary — the VERDICT target for
        negotiated ragged allgather / uneven alltoall."""
        results = run(_battery, args=("t8",),
                      hosts="localhost:4,127.0.0.1:4")
        assert len(results) == 2
        for (tag, rank, n, pc, passed), want_rank in zip(results, (0, 4)):
            assert (tag, rank, n, pc) == ("t8", want_rank, 8, 2)
            assert passed == ALL_OPS


def _kv_traffic_probe(reps):
    """Per-collective control-plane traffic from this process's view:
    {op: (rounds_per_call, payload_bytes_per_round)}. Runs each op
    ``reps`` times so per-call averages smooth one-time setup rounds."""
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.common import negotiation

    n = hvd.size()
    lr = hvd.topology().local_device_ranks
    nl = len(lr)
    out = {}

    def measure(name, fn):
        fn()                     # warm: compile + any one-time rounds
        negotiation.stats_reset()
        for _ in range(reps):
            fn()
        s = negotiation.stats_snapshot()
        out[name] = (s["rounds"] / reps,
                     s["payload_bytes"] / max(s["rounds"], 1),
                     s["gets"] / max(s["rounds"], 1),
                     (s["fusion_sets"] + s["fusion_gets"]) / reps)

    x = np.ones((nl, 3), np.float32)
    measure("allreduce", lambda: hvd.allreduce(x, op=hvd.Sum))
    measure("allgather", lambda: hvd.allgather(x))
    measure("reducescatter",
            lambda: hvd.reducescatter(np.ones((nl, 2 * n), np.float32),
                                      op=hvd.Sum))
    ragged = [np.full((r + 1, 2), float(r), np.float32) for r in lr]
    measure("allgather_ragged", lambda: hvd.allgather_ragged(ragged))
    send = np.ones((nl, n), np.float32)
    splits = np.ones((nl, n), int)
    measure("alltoall_uneven", lambda: hvd.alltoall(send, splits=splits))
    measure("allgather_object",
            lambda: hvd.allgather_object([hvd.rank()]))
    # Async path: no negotiation rounds; its control-plane cost is the
    # fusion boundary publish/consume traffic (O(1) per flush, counted
    # via negotiation.record_fusion_kv).
    measure("allreduce_async",
            lambda: hvd.allreduce_async(x, op=hvd.Sum).synchronize())
    return out


class TestControlPlaneScaling:
    """VERDICT r4 item 2: the control plane must scale like the
    reference's coordinator (reference: controller.cc:74 — one negotiation
    per ready batch regardless of world size). Negotiation ROUNDS per
    collective are O(1) in world size — static-shape collectives do ZERO
    KV traffic (compiled programs replace per-op negotiation) — and
    per-rank payloads stay bytes-sized."""

    W2 = "localhost:1,127.0.0.1:1"
    W4 = "localhost:1,127.0.0.1:1,127.0.0.2:1,127.0.0.3:1"
    W8 = ",".join(f"127.0.0.{i}:1" for i in range(1, 9))

    def _check(self, per_rank, world):
        for stats in per_rank:
            # Compiled static-shape programs need no per-op negotiation.
            for op in ("allreduce", "allgather", "reducescatter",
                       "allreduce_async"):
                assert stats[op][0] == 0, (op, world, stats[op])
            # Dynamic-shape ops: exactly one size-exchange round per call,
            # reading each peer's vector once (world-1 gets per round).
            for op in ("allgather_ragged", "alltoall_uneven"):
                assert stats[op][0] == 1, (op, world, stats[op])
                assert stats[op][2] == world - 1, (op, world, stats[op])
            # Payloads are per-rank size vectors: bytes, not tensors.
            # Fusion boundary traffic: O(1) KV ops per flushed async op
            # (coordinator publishes once, followers consume once) — the
            # bound is loose (debounced cycle thread may add a poll) but
            # catches any O(world) or per-tensor regression.
            for op, (rounds, payload, _gets, fusion) in stats.items():
                if rounds:
                    assert payload <= 64 * world, (op, world, payload)
                assert fusion <= 3, (op, world, fusion)
        return per_rank[0]

    @pytest.mark.timeout(600)
    def test_kv_rounds_constant_world2_vs_world4(self, shared_cluster):
        r2 = self._check(
            shared_cluster(self.W2).run(_kv_traffic_probe, args=(3,)), 2)
        r4 = self._check(
            shared_cluster(self.W4).run(_kv_traffic_probe, args=(3,)), 4)
        for op in r2:
            assert r2[op][0] == r4[op][0], (op, r2[op], r4[op])

    @pytest.mark.timeout(600)
    def test_kv_rounds_world8_equal_world2(self, shared_cluster):
        """The verdict's literal bar: KV message counts at world 8 equal
        world 2 — eight real jax.distributed processes."""
        r2 = self._check(
            shared_cluster(self.W2).run(_kv_traffic_probe, args=(3,)), 2)
        r8 = self._check(
            run(_kv_traffic_probe, args=(3,), hosts=self.W8), 8)
        for op in r2:
            assert r2[op][0] == r8[op][0], (op, r2[op], r8[op])


def _hier_kv_probe(reps):
    """Per-tier control-plane traffic from this process's view under the
    cluster's forced slice layout, plus flat-vs-hier payload parity:
    returns ``(proc, groups, stats, parity_ok, hier_out)``."""
    import os

    import jax
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.common import control_plane, negotiation

    me = jax.process_index()
    procs = list(range(jax.process_count()))
    groups = control_plane.exchange_groups(procs)
    lr = hvd.topology().local_device_ranks
    ragged = [np.full((r + 1, 2), float(r), np.float32) for r in lr]
    x = np.ones((len(lr), 3), np.float32)
    # Warm: compile + first boundary publish/consume.
    hvd.allgather_ragged(ragged)
    hvd.allreduce_async(x, op=hvd.Sum).synchronize()
    negotiation.stats_reset()
    for _ in range(reps):
        hvd.allgather_ragged(ragged)          # 1 negotiation round each
        hvd.allreduce_async(x, op=hvd.Sum).synchronize()  # boundary sync
    stats = negotiation.stats_snapshot()
    # Bit-identical payload orderings: the SAME payload exchanged under
    # hier then flat (every process flips the knob at the same point —
    # SPMD) must produce the identical ordered list.
    payload = {"p": me, "sizes": [me + 1, 2 * me, 7]}
    os.environ["HOROVOD_CONTROL_PLANE"] = "hier"
    hier_out = negotiation.exchange("cp_parity", payload)
    os.environ["HOROVOD_CONTROL_PLANE"] = "flat"
    flat_out = negotiation.exchange("cp_parity", payload)
    os.environ.pop("HOROVOD_CONTROL_PLANE", None)
    return (me, groups, stats, flat_out == hier_out, hier_out)


class TestHierControlPlane:
    """The hierarchical control plane (ISSUE 14 tentpole): when a slice
    hierarchy exists, negotiation decomposes into slice-local + leaders-
    only rounds — member gets are O(1) per round, leader gets are
    O(slice_size + num_slices), never O(world) — and the fusion boundary
    stream reaches members through their slice leader's re-publish (a
    member's blocking reads of the ROOT boundary key are ZERO)."""

    W4 = "localhost:1,127.0.0.1:1,127.0.0.2:1,127.0.0.3:1"
    W8 = ",".join(f"127.0.0.{i}:1" for i in range(1, 9))

    def _roles(self, groups, coordinator=0):
        """(negotiation leaders, fusion leaders, fusion members)."""
        neg_leaders = {g[0] for g in groups}
        fus_leaders, fus_members = set(), set()
        for g in groups:
            followers = [p for p in g if p != coordinator]
            if followers:
                fus_leaders.add(followers[0])
                fus_members.update(followers[1:])
        return neg_leaders, fus_leaders, fus_members

    def _check_hier(self, per_rank, world, slices, reps):
        per = world // slices
        groups0 = per_rank[0][1]
        assert groups0 is not None and len(groups0) == slices, groups0
        neg_leaders, fus_leaders, fus_members = self._roles(groups0)
        for me, groups, stats, parity_ok, hier_out in per_rank:
            assert groups == groups0, (me, groups)
            assert parity_ok, (me, "flat and hier payloads diverged")
            assert hier_out == per_rank[0][4], (me, "hier_out diverged")
            assert stats["hier_rounds"] == reps, (me, stats)
            if me in neg_leaders:
                # Slice-local gather + ONE leaders-only DCN round.
                assert stats["gets_local"] == (per - 1) * reps, (me, stats)
                assert stats["gets_cross"] == (slices - 1) * reps, \
                    (me, stats)
                assert stats["gets_fanback"] == 0, (me, stats)
                # The headline bound: never O(world).
                assert stats["gets"] == ((per - 1) + (slices - 1)) * reps
                assert stats["gets"] < (world - 1) * reps
            else:
                # Members: O(1) blocking gets per round.
                assert stats["gets_fanback"] == reps, (me, stats)
                assert stats["gets"] == reps, (me, stats)
            if me in fus_members:
                # Boundary stream through the slice leader's re-publish:
                # member load on the coordinator's root key is ZERO.
                assert stats["fusion_root_gets"] == 0, (me, stats)
                assert stats["fusion_slice_gets"] > 0, (me, stats)
            elif me in fus_leaders:
                assert stats["fusion_root_gets"] > 0, (me, stats)
                assert stats["fusion_slice_gets"] == 0, (me, stats)
        return per_rank[0][2]

    @pytest.mark.timeout(600)
    def test_world4_slices2_member_gets_o1(self, shared_cluster):
        per_rank = shared_cluster(
            self.W4, extra_env={"HOROVOD_MESH_SLICES": "2"}).run(
            _hier_kv_probe, args=(3,))
        self._check_hier(per_rank, 4, 2, 3)

    @pytest.mark.slow
    @pytest.mark.timeout(600)
    def test_world8_leader_gets_scale_with_slices_not_world(
            self, shared_cluster):
        """ISSUE 14 guard leg: world 8 under slices 2 vs 4 — member gets
        stay constant (O(1)); leader cross gets move with the slice
        count (1 vs 3 per round), never the world size (7)."""
        r2 = self._check_hier(shared_cluster(
            self.W8, extra_env={"HOROVOD_MESH_SLICES": "2"}).run(
            _hier_kv_probe, args=(3,)), 8, 2, 3)
        r4 = self._check_hier(shared_cluster(
            self.W8, extra_env={"HOROVOD_MESH_SLICES": "4"}).run(
            _hier_kv_probe, args=(3,)), 8, 4, 3)
        # Proc 0 leads its slice in both layouts: its cross fan-out
        # follows num_slices - 1 exactly (1 vs 3 per round), its local
        # fan-out the slice size (3 vs 1) — neither follows world - 1.
        assert r2["gets_cross"] == 1 * 3 and r4["gets_cross"] == 3 * 3, \
            (r2, r4)
        assert r2["gets_local"] == 3 * 3 and r4["gets_local"] == 1 * 3, \
            (r2, r4)


class TestControlPlaneDryrun:
    """n=128-512 virtual-world dryrun (docs/scale_validation.md): the
    REAL exchange implementations driven by one thread per virtual rank
    over an in-memory KV. The perf guard: KV RPCs per negotiation round
    scale with slice count, not world size, and member-rank gets are
    constant across worlds at fixed slice size."""

    @pytest.mark.timeout(120)
    def test_n128_member_o1_leader_scales_with_slices(self):
        from horovod_tpu.common import control_plane as cp
        r = cp.simulate_exchange(128, 8, rounds=2)
        assert r["identical"], "ranks disagreed on the payload ordering"
        assert r["member_gets_per_round"] == 1
        assert r["leader_gets_per_round"] == (128 // 8 - 1) + (8 - 1)
        plan = cp.exchange_plan(128, 8)
        assert plan["member_gets"] == 1
        assert plan["leader_gets"] == r["leader_gets_per_round"]
        # The flat schedule at the same world: the cliff being removed.
        assert plan["leader_gets"] < 127

    @pytest.mark.timeout(300)
    def test_n512_green_member_gets_constant_at_fixed_slice_size(self):
        from horovod_tpu.common import control_plane as cp
        # slice_size 32 at both worlds: member gets constant, leader
        # LOCAL gets constant, only the cross fan-out moves (4 -> 16
        # slices), and it moves with the slice count.
        r128 = cp.simulate_exchange(128, 4, rounds=1)
        r512 = cp.simulate_exchange(512, 16, rounds=1)
        assert r128["identical"] and r512["identical"]
        assert r128["slice_size"] == r512["slice_size"] == 32
        assert r128["member_gets_per_round"] == \
            r512["member_gets_per_round"] == 1
        assert r512["leader_gets_per_round"] - \
            r128["leader_gets_per_round"] == (16 - 1) - (4 - 1)
        # Total round RPCs grew sub-linearly: 4x world, < 4x gets would
        # hold even flat — assert the per-rank MAX is what collapsed.
        assert max(c["gets"] for c in r512["per_proc"]) == 31 + 15

    @pytest.mark.timeout(120)
    def test_flat_vs_hier_bit_identical_payloads(self):
        from horovod_tpu.common import control_plane as cp
        f = cp.simulate_exchange(128, 0, rounds=1, strategy="flat")
        h = cp.simulate_exchange(128, 8, rounds=1)
        assert f["result"] == h["result"]
        # And the flat baseline really is the O(world) schedule the
        # hierarchy removes.
        assert f["member_gets_per_round"] == 127

    # --- twin anchor: these thread legs are the ground truth the hvdsim
    # event twin must reproduce before its 16k-65k extrapolations
    # (tests/test_sim.py) are worth anything. Compare everything except
    # "attempts": the flat thread path's bounded short-timeout sweep
    # retries are timing-dependent by design; the gets the guards count
    # are not.

    @staticmethod
    def _assert_twin_matches_thread(thread, twin):
        for key in ("world", "num_slices", "slice_size", "strategy",
                    "rounds", "identical", "payload_bytes", "gets_total",
                    "member_gets_per_round", "leader_gets_per_round"):
            assert thread[key] == twin[key], \
                (key, thread[key], twin[key])
        assert thread["result"] == twin["result"]
        for tc, wc in zip(thread["per_proc"], twin["per_proc"]):
            for key in ("sets", "gets", "gets_local", "gets_cross",
                        "gets_fanback"):
                assert tc[key] == wc[key], (key, tc, wc)

    @pytest.mark.timeout(120)
    def test_twin_matches_thread_dryrun_n128(self):
        from horovod_tpu.common import control_plane as cp
        from horovod_tpu.sim.control import twin_exchange
        self._assert_twin_matches_thread(
            cp.simulate_exchange(128, 8, rounds=2),
            twin_exchange(128, 8, rounds=2))
        self._assert_twin_matches_thread(
            cp.simulate_exchange(128, 0, rounds=1, strategy="flat"),
            twin_exchange(128, 0, rounds=1, strategy="flat"))

    @pytest.mark.timeout(300)
    def test_twin_matches_thread_dryrun_n512(self):
        from horovod_tpu.common import control_plane as cp
        from horovod_tpu.sim.control import twin_exchange
        self._assert_twin_matches_thread(
            cp.simulate_exchange(512, 16, rounds=1),
            twin_exchange(512, 16, rounds=1))


def _frontend_battery():
    """Frontend eager ops across a real process boundary: the stacked-rows
    and splits-matrix contracts (local rows only) for torch/tf/mxnet."""
    import numpy as np
    import horovod_tpu as hvd

    n = hvd.size()
    results = []

    # torch frontend
    import torch
    import horovod_tpu.torch as ht
    t = torch.ones(3) * (hvd.rank() + 1)
    out = ht.allreduce(t, op=ht.Sum)
    # The host tensor replicates onto each local chip, so the reduction
    # weights each process's value (its first local rank + 1) by its chip
    # count; ownership is process-major contiguous.
    per = n // hvd.process_count()
    want = float(sum((pr * per + 1) * per
                     for pr in range(hvd.process_count())))
    assert torch.allclose(out, torch.full((3,), want)), (out, want)
    results.append("torch_allreduce")

    # torch alltoall with splits (uniform 1-row splits)
    send = torch.arange(n * 2, dtype=torch.float32).reshape(n, 2)
    rows, received = ht.alltoall(send, splits=[1] * n)
    assert rows.shape == (n, 2)
    assert received.tolist() == [1] * n
    results.append("torch_alltoall_splits")

    # mxnet duck-typed frontend (numpy NDArray stand-in)
    import horovod_tpu.mxnet as hm
    arr = np.ones((2, 2), np.float32)
    out = hm.allreduce(arr, op=hm.Sum, name="mx")
    np.testing.assert_allclose(out, np.full((2, 2), float(n)))
    o2, rs = hm.alltoall(np.arange(n, dtype=np.float32)[:, None],
                         splits=[1] * n)
    assert rs.tolist() == [1] * n
    results.append("mxnet_ops")

    # tf frontend (eager + splits matrix contract)
    import tensorflow as tf
    import horovod_tpu.tensorflow as htf
    o = htf.allreduce(tf.ones((2,)), op=htf.Sum)
    np.testing.assert_allclose(o.numpy(), [n, n])
    vals, rec = htf.alltoall(tf.reshape(
        tf.range(n * 2, delta=1.0), (n, 2)), splits=[1] * n)
    assert rec.numpy().tolist() == [1] * n
    results.append("tf_ops")

    return (hvd.rank(), results)


class TestMultiProcessFrontends:
    def test_frontend_contracts_two_processes(self, shared_cluster):
        results = shared_cluster(H22).run(_frontend_battery)
        want = ["torch_allreduce", "torch_alltoall_splits", "mxnet_ops",
                "tf_ops"]
        assert [r[1] for r in results] == [want, want]


def _negotiation_churn():
    """Repeated same-tag exchanges: the lag-2 coordination-key deletion
    must never remove a key a peer still needs."""
    import horovod_tpu as hvd
    out = None
    for i in range(5):
        out = hvd.allgather_object([i * 10 + hvd.rank()])
    return out


class TestNegotiationChurn:
    def test_repeated_exchanges_with_key_gc(self):
        results = run(_negotiation_churn, hosts="localhost:1,127.0.0.1:1")
        assert results == [[40, 41], [40, 41]]


def _order_check_worker(diverge):
    # HOROVOD_ORDER_CHECK rides extra_env: the task bootstrap calls
    # hvd.init() before the user fn, so in-fn environ tweaks are too late.
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.common.exceptions import TensorShapeMismatchError
    nl = len(hvd.topology().local_device_ranks)
    ok = np.asarray(hvd.allreduce(np.ones((nl, 3), np.float32), op=hvd.Sum))
    assert ok[0, 0] == hvd.size()
    if not diverge:
        hvd.allreduce(np.ones((nl, 2), np.float32))
        return "matched"
    try:
        # Rank 0 dispatches allreduce; rank 1 an allgather of a different
        # trailing shape at the same program point.
        if hvd.cross_rank() == 0:
            hvd.allreduce(np.ones((nl, 2), np.float32))
        else:
            hvd.allgather(np.ones((nl, 5), np.float32))
        return "no-error"
    except TensorShapeMismatchError:
        return "caught"


class TestOrderCheck:
    def test_matched_order_passes(self):
        results = run(_order_check_worker, args=(False,),
                      hosts="localhost:1,127.0.0.1:1",
                      extra_env={"HOROVOD_ORDER_CHECK": "1"})
        assert results == ["matched", "matched"]

    def test_diverged_order_raises_on_every_rank(self):
        results = run(_order_check_worker, args=(True,),
                      hosts="localhost:1,127.0.0.1:1",
                      extra_env={"HOROVOD_ORDER_CHECK": "1"})
        assert results == ["caught", "caught"]


def _mlp_setup():
    """Shared worker setup: broadcast-identical MLP params, loss fn, and a
    host-replicated global batch (the JIT-path input contract)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.models import MLP
    from horovod_tpu.optim import broadcast_parameters

    mesh = hvd.global_process_set.mesh
    n = hvd.size()
    model = MLP(features=[8, 4])
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 6)))["params"]
    params = broadcast_parameters(params, root_rank=0)

    def loss_fn(p, batch):
        logits = model.apply({"params": p}, batch["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()

    rng = np.random.default_rng(0)
    batch = {"x": jnp.asarray(rng.standard_normal((2 * n, 6)), jnp.float32),
             "y": jnp.asarray(rng.integers(0, 4, (2 * n,)), jnp.int32)}
    return mesh, params, loss_fn, batch


def _train_step_worker():
    """The flagship path — DistributedOptimizer + make_train_step — across
    a REAL process boundary (the `hvdrun -H a:2,b:2 python train.py` case).
    Each process feeds the full (host-replicated) global batch; shard_map
    shards compute; the fused gradient allreduce crosses processes."""
    import optax
    from horovod_tpu.optim import DistributedOptimizer
    from horovod_tpu.parallel import TrainState, make_train_step

    mesh, params, loss_fn, batch = _mlp_setup()
    opt = DistributedOptimizer(optax.sgd(0.1))
    step = make_train_step(loss_fn, opt, mesh, donate=False)
    state = TrainState.create(params, opt)
    losses = []
    for _ in range(3):
        state, loss = step(state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses  # actually training
    return round(losses[-1], 6)


def _zero_step_worker():
    """ZeRO-1 across a real process boundary: reduce-scattered grads and
    1/n-sharded moments with the mesh spanning two processes."""
    import optax
    from horovod_tpu.parallel import ZeroTrainState, make_zero_train_step

    mesh, params, loss_fn, batch = _mlp_setup()
    tx = optax.adam(1e-2)
    step = make_zero_train_step(loss_fn, tx, mesh, donate=False)
    state = ZeroTrainState.create(params, tx, mesh)
    for _ in range(2):
        state, loss = step(state, batch)
    return round(float(loss), 6)


def _fsdp_step_worker():
    """FSDP/ZeRO-3 across a real process boundary: params, grads and adam
    moments sharded over a mesh spanning two processes; GSPMD's gathers
    and reduce-scatters cross the boundary."""
    import optax
    from horovod_tpu.parallel.fsdp import make_fsdp_train_step, shard_batch

    mesh, params, loss_fn, batch = _mlp_setup()
    tx = optax.adam(1e-2)
    init_fn, step_fn = make_fsdp_train_step(loss_fn, tx, mesh, min_size=8,
                                            donate=False)
    sp, so = init_fn(params)
    assert not sp["Dense_0"]["kernel"].sharding.is_fully_replicated
    gbatch = shard_batch(batch, mesh)
    losses = []
    for _ in range(3):
        sp, so, loss = step_fn(sp, so, gbatch)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    return round(losses[-1], 6)


class TestMultiProcessTrainStep:
    def test_dp_train_step_crosses_processes(self, shared_cluster):
        results = shared_cluster(H22).run(_train_step_worker)
        assert len(results) == 2
        assert results[0] == results[1]  # identical replicated updates

    def test_zero_train_step_crosses_processes(self, shared_cluster):
        results = shared_cluster(H22).run(_zero_step_worker)
        assert len(results) == 2
        assert results[0] == results[1]

    def test_fsdp_train_step_crosses_processes(self, shared_cluster):
        results = shared_cluster(H22).run(_fsdp_step_worker)
        assert len(results) == 2
        assert results[0] == results[1]


def _composite_worker():
    """dp x pp x tp (+ EP) GPT training step with the 3-D mesh spanning two
    REAL processes — pipeline hops and TP reductions cross the boundary."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.models.gpt import GPTConfig
    from horovod_tpu.parallel.composite import CompositeGPT, build_mesh3d

    dp, pp, tp = 1, 2, 2
    assert hvd.size() == dp * pp * tp
    cfg = GPTConfig.tiny(vocab_size=32, hidden_size=16, num_layers=2,
                         num_heads=2, intermediate_size=32,
                         max_position_embeddings=8,
                         num_experts=2 * dp, capacity_factor=4.0)
    mesh3 = build_mesh3d(dp, pp, tp)
    comp = CompositeGPT(cfg, mesh3, optax.adam(1e-3), n_micro=2)
    ids = jnp.asarray(np.random.default_rng(2).integers(
        0, 32, (2 * dp, 8)), jnp.int32)
    params, opt_state, specs = comp.init(jax.random.PRNGKey(1), ids)
    step = comp.make_train_step(specs, donate=False)
    _, _, loss = step(params, opt_state, ids)
    assert np.isfinite(float(loss))
    return round(float(loss), 5)


class TestMultiProcessComposite:
    def test_3d_mesh_spans_processes(self, shared_cluster):
        results = shared_cluster(H22).run(_composite_worker)
        assert len(results) == 2
        assert results[0] == results[1]


def _ring_attention_worker():
    """Ring attention with the sp ring crossing a real process boundary:
    K/V blocks ppermute between processes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    import horovod_tpu as hvd
    from horovod_tpu.parallel.sequence import ring_attention

    n = hvd.size()
    devices = hvd.global_process_set.mesh.devices.reshape(-1)
    mesh = Mesh(devices, ("sp",))
    D, H = 8, 2
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((D, 3 * D)) * 0.1, jnp.float32)
    xs = jnp.asarray(rng.standard_normal((1, 4 * n, D)), jnp.float32)

    def heads(t):
        return t.reshape(t.shape[:-1] + (H, D // H))

    def loss(w, xl):
        q, k, v = jnp.split(xl @ w, 3, axis=-1)
        o = ring_attention(heads(q), heads(k), heads(v), axis_name="sp",
                           causal=True)
        return jax.lax.pmean(jnp.mean(o.astype(jnp.float32) ** 2), "sp")

    # check_vma=False: the VMA checker can't infer replication
    # through grad-of-ppermute chains (the gap dp.py documents). Without
    # the checker, the transpose of the replicated-w broadcast no longer
    # inserts its psum, so the grad is summed explicitly — the
    # cross-process value equality below is the real replication check.
    def grad_fn(w, xl):
        return jax.lax.psum(jax.grad(loss)(w, xl), "sp")

    g = jax.jit(jax.shard_map(
        grad_fn, mesh=mesh,
        in_specs=(P(), P(None, "sp", None)), out_specs=P(),
        check_vma=False))(w, xs)
    assert np.isfinite(np.asarray(g)).all()
    return round(float(np.asarray(g).sum()), 5)


def _sp_gpt_worker():
    """The flagship long-context path across a REAL process boundary: GPT
    with sp_axis sharding tokens over a mesh spanning two processes —
    flash-ring hops, global position offsets, and boundary-correct labels
    all cross the wire."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P
    import horovod_tpu as hvd
    from horovod_tpu.models.gpt import GPT, GPTConfig
    from horovod_tpu.parallel import next_token_labels

    n = hvd.size()
    devices = hvd.global_process_set.mesh.devices.reshape(-1)
    mesh = Mesh(devices, ("sp",))
    cfg = GPTConfig.tiny(tp_axis=None, ep_axis=None, num_heads=4,
                         hidden_size=32, sp_axis="sp", sp_impl="ring",
                         use_flash=True, max_position_embeddings=8 * n)
    model = GPT(cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 256, (1, 8 * n)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids[:, :8])["params"]

    def loss(p, i):
        logits = model.apply({"params": p}, i)
        labels = next_token_labels(i, axis_name="sp")
        mask = labels != -100
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), jnp.maximum(labels, 0))
        return lax.psum(jnp.sum(ce * mask), "sp") / lax.psum(
            jnp.sum(mask.astype(jnp.float32)), "sp")

    # check_vma=False: psum-normalized loss and grads ARE replicated, but
    # the VMA checker can't infer it through the flash-ring's
    # ppermute/psum chains (the dp.py gap); rank equality below is the
    # real check.
    val, grads = jax.jit(jax.shard_map(
        jax.value_and_grad(loss), mesh=mesh,
        in_specs=(P(), P(None, "sp")), out_specs=(P(), P()),
        check_vma=False))(params, ids)
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in leaves)
    return round(float(val), 5)


class TestMultiProcessSequenceParallel:
    @pytest.mark.timeout(600)   # ~90s solo; headroom for parallel CI shards
    def test_sp_gpt_crosses_processes(self, shared_cluster):
        # cluster-job timeout must match the marker, or the cluster's own
        # 300s default fires first and marks the shared cluster dead
        results = shared_cluster(H22).run(_sp_gpt_worker, timeout=580)
        assert len(results) == 2
        assert results[0] == results[1]

    def test_ring_attention_crosses_processes(self, shared_cluster):
        results = shared_cluster(H22).run(_ring_attention_worker)
        assert len(results) == 2
        assert results[0] == results[1]


def _torus_worker():
    """2-level torus allreduce over the (cross, local) mesh with the cross
    axis spanning real processes (the fork's NCCLTorusAllreduce analog)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    import horovod_tpu as hvd
    from horovod_tpu.parallel import allreduce_torus

    n = hvd.size()
    mesh2d = hvd.topology().mesh2d

    def torus(xl):
        return allreduce_torus(jnp.squeeze(xl, 0))[None]

    g = jax.jit(jax.shard_map(
        torus, mesh=mesh2d, in_specs=P(("cross", "local")),
        out_specs=P(("cross", "local"))))(
            jnp.arange(n * 4, dtype=jnp.float32).reshape(n, 4))
    expect = np.arange(n * 4).reshape(n, 4).sum(0)
    # every process checks its addressable shards against the expectation
    # (fetching the full global array would touch non-addressable devices)
    for shard in g.addressable_shards:
        np.testing.assert_allclose(np.asarray(shard.data)[0], expect,
                                   rtol=1e-5)
    return "ok"


class TestMultiProcessTorus:
    def test_torus_allreduce_crosses_processes(self, shared_cluster):
        results = shared_cluster(H22).run(_torus_worker)
        assert results == ["ok", "ok"]


def _ulysses_worker():
    """Ulysses all-to-all sequence parallelism with the head scatter
    crossing a real process boundary."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    import horovod_tpu as hvd
    from horovod_tpu.parallel.sequence import ulysses_attention

    n = hvd.size()
    devices = hvd.global_process_set.mesh.devices.reshape(-1)
    mesh = Mesh(devices, ("sp",))
    D, H = 8, 4  # heads divisible by n=4
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 4 * n, H, D // H)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 4 * n, H, D // H)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 4 * n, H, D // H)), jnp.float32)

    def f(q, k, v):
        return ulysses_attention(q, k, v, axis_name="sp", causal=True)

    o = jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp")))(q, k, v)
    # Numeric check: Ulysses is exact, so every addressable shard must
    # equal the corresponding slice of plain full attention.
    from horovod_tpu.parallel.sequence import local_attention
    expect = np.asarray(local_attention(q, k, v, causal=True))
    for shard in o.addressable_shards:
        np.testing.assert_allclose(np.asarray(shard.data),
                                   expect[shard.index], rtol=1e-4,
                                   atol=1e-5)
    return "ok"


class TestMultiProcessUlysses:
    def test_ulysses_crosses_processes(self, shared_cluster):
        results = shared_cluster(H22).run(_ulysses_worker)
        assert results == ["ok", "ok"]


def _adasum_worker():
    """Adasum (scale-invariant combine) across a real process boundary,
    checked against the host-side tree ground truth."""
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.ops.adasum import adasum_tree

    n = hvd.size()
    lr = hvd.topology().local_device_ranks
    rows = np.stack([np.arange(1.0, 4.0) * (r + 1) for r in lr]).astype(
        np.float32)
    out = np.asarray(hvd.allreduce(rows, op=hvd.Adasum))
    expect = adasum_tree([np.arange(1.0, 4.0) * (r + 1)
                          for r in range(n)])
    for row in out:
        np.testing.assert_allclose(row, expect, rtol=1e-5)
    return "ok"


class TestMultiProcessAdasum:
    def test_adasum_crosses_processes(self, shared_cluster):
        results = shared_cluster(H22).run(_adasum_worker)
        assert results == ["ok", "ok"]


def _process_set_worker():
    """Process-set collectives multi-process: a set spanning both processes
    reduces over its sub-mesh; a set owned by ONE process runs without the
    other participating (exchange scoped to the set's owners)."""
    import numpy as np
    import horovod_tpu as hvd

    lr = hvd.topology().local_device_ranks
    spanning = hvd.add_process_set(hvd.ProcessSet([1, 2]))  # one rank each
    try:
        mine = [r for r in lr if r in (1, 2)]
        if mine:
            rows = np.stack([np.full((2,), float(r + 1))
                             for r in mine]).astype(np.float32)
            out = np.asarray(hvd.allreduce(rows, op=hvd.Sum,
                                           process_set=spanning))
            np.testing.assert_allclose(out, np.full((len(mine), 2), 5.0))
    finally:
        hvd.remove_process_set(spanning)

    local_only = hvd.add_process_set(hvd.ProcessSet(lr))  # this proc's ranks
    try:
        rows = np.stack([np.full((2,), 1.0) for _ in lr]).astype(np.float32)
        out = np.asarray(hvd.allreduce(rows, op=hvd.Sum,
                                       process_set=local_only))
        np.testing.assert_allclose(out, np.full((len(lr), 2), float(len(lr))))
    finally:
        hvd.remove_process_set(local_only)
    return "ok"


class TestMultiProcessProcessSets:
    def test_process_sets_cross_and_local(self, shared_cluster):
        results = shared_cluster(H22).run(_process_set_worker)
        assert results == ["ok", "ok"]
