"""hvdlint: the program analyzer (hvd.check_program) and the AST lint.

Known-bad / known-good corpus: every rule class has a positive (flagged)
and a negative (clean) case; plus the tier-1 self-lint gate over the repo
scope and a multi-process cross-check that the analyzer's predicted
collective sequence matches the flight recorder's recorded one."""

import json
import os
import sys
import time

import cloudpickle
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

# Worker processes can't import this module by name; ship the cross-check
# job (and anything else defined here) by value.
cloudpickle.register_pickle_by_value(sys.modules[__name__])

from horovod_tpu.analysis import events as an_events
from horovod_tpu.analysis.lint import (declared_knobs, lint_paths,
                                       lint_source)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _codes(findings):
    return {f.code for f in findings}


# ---------------------------------------------------------------------------
# Program analyzer (hvd.check_program)
# ---------------------------------------------------------------------------


class TestCheckProgram:
    def test_rank_conditional_deadlock_flagged(self, hvd):
        """Acceptance: the PR-4 chaos soak's failure shape — a collective
        only rank 0 dispatches — is flagged statically with rank + seq +
        op named; the equivalent unconditional program passes clean."""
        x = np.ones((4, 8), np.float32)

        def bad_step(x):
            y = hvd.allreduce(x)
            if hvd.rank() == 0:
                y = y + hvd.allreduce(x * 2)
            return y

        def good_step(x):
            y = hvd.allreduce(x)
            y = y + hvd.allreduce(x * 2)
            return y

        rep = hvd.check_program(bad_step, (x,), world_size=4)
        assert not rep.ok
        err = rep.errors()[0]
        assert err.code == "HVP101"
        assert err.rank == 0
        assert err.op == "allreduce"
        assert err.seq == 2
        assert err.ps == "global"
        assert err.sig is not None
        # the identity fields also appear in the rendered message
        assert "allreduce" in err.message and "seq 2" in err.message

        rep2 = hvd.check_program(good_step, (x,), world_size=4)
        assert rep2.ok and not rep2.findings

    def test_order_mismatch(self, hvd):
        x = np.ones((4, 8), np.float32)

        def bad(x):
            if hvd.rank() % 2 == 0:
                hvd.allreduce(x)
                hvd.allgather(x)
            else:
                hvd.allgather(x)
                hvd.allreduce(x)
            return x

        def good(x):
            hvd.allreduce(x)
            hvd.allgather(x)
            return x

        assert "HVP102" in _codes(
            hvd.check_program(bad, (x,), world_size=4).findings)
        rep = hvd.check_program(good, (x,), world_size=4)
        assert rep.ok

    def test_dtype_mismatch(self, hvd):
        x = np.ones((4, 8), np.float32)

        def bad(x):
            y = x.astype(jnp.bfloat16) if hvd.rank() == 1 else x
            return hvd.allreduce(y)

        def good(x):
            return hvd.allreduce(x.astype(jnp.bfloat16))

        assert "HVP103" in _codes(
            hvd.check_program(bad, (x,), world_size=4).findings)
        assert hvd.check_program(good, (x,), world_size=4).ok

    def test_degenerate_process_set(self, hvd):
        x = np.ones((4, 8), np.float32)
        ps1 = hvd.ProcessSet([0])
        ps2 = hvd.ProcessSet([0, 1])

        def bad(x):
            return hvd.allreduce(x[:1], process_set=ps1)

        def good(x):
            return hvd.allreduce(x[:2], process_set=ps2)

        assert "HVP104" in _codes(
            hvd.check_program(bad, (x,), world_size=4).findings)
        assert "HVP104" not in _codes(
            hvd.check_program(good, (x,), world_size=4).findings)

    def test_fusion_fill_advisory(self, hvd):
        from horovod_tpu.common.config import Config
        cfg = Config()
        big = np.ones((4, 1024), np.float32)

        def bad(x):
            for _ in range(9):
                x = hvd.allreduce(x) * 0 + x  # fresh buffer each round
            return x

        def good(x):
            return hvd.allreduce(x)

        rep = hvd.check_program(bad, (big,), world_size=4, config=cfg)
        assert "HVP105" in _codes(rep.findings)
        assert rep.ok  # advisory only
        assert "HVP105" not in _codes(
            hvd.check_program(good, (big,), world_size=4,
                              config=cfg).findings)

    def test_wire_dtype_advisory(self, hvd):
        from horovod_tpu.common.config import Config
        mesh = Mesh(np.array(jax.devices()[:4]), ("hvd",))
        x = np.ones((4, 8), np.float32)

        def jit_step(x):
            def inner(xl):
                return lax.psum(xl, "hvd")
            return jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=P("hvd"), out_specs=P()))(x)

        cfg = Config(wire_dtype="bf16")
        assert "HVP106" in _codes(
            hvd.check_program(jit_step, (x,), world_size=4,
                              config=cfg).findings)
        # no compression configured -> no advisory
        assert "HVP106" not in _codes(
            hvd.check_program(jit_step, (x,), world_size=4,
                              config=Config()).findings)

    def test_wire_dtype_advisory_suppressed_by_quantized_exchange(
            self, hvd):
        """HVP106 must NOT fire when the jaxpr shows the block-scaled
        exchange (int8 collectives from ops/wire.py): that program is
        already quantizing in jit — the fp32 collectives alongside are
        its own block scales."""
        from horovod_tpu.common.config import Config
        from horovod_tpu.parallel.strategies import allreduce_quantized
        mesh = Mesh(np.array(jax.devices()[:4]), ("hvd",))
        x = np.ones((4, 4096), np.float32)

        def quant_step(x):
            def inner(xl):
                return allreduce_quantized(
                    xl.reshape(-1), axis_name="hvd").reshape(xl.shape)
            return jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"),
                check_vma=False))(x)

        cfg = Config(wire_dtype="int8")
        cfg.wire_error_feedback = False
        codes = _codes(hvd.check_program(quant_step, (x,), world_size=4,
                                         config=cfg).findings)
        assert "HVP106" not in codes
        assert "HVP109" not in codes   # EF off -> no residual advisory

    def test_stale_residual_advisory_hvp109(self, hvd):
        """HVP109: error feedback configured + in-jit quantized exchange
        -> advisory that residuals live outside the runtime store (stale
        on elastic reset unless the optimizer zeroes them). Advisory
        only: the report stays ok."""
        from horovod_tpu.common.config import Config
        from horovod_tpu.parallel.strategies import allreduce_quantized
        mesh = Mesh(np.array(jax.devices()[:4]), ("hvd",))
        x = np.ones((4, 4096), np.float32)

        def quant_step(x):
            def inner(xl):
                return allreduce_quantized(
                    xl.reshape(-1), axis_name="hvd").reshape(xl.shape)
            return jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"),
                check_vma=False))(x)

        cfg = Config(wire_dtype="int8")
        cfg.wire_error_feedback = True
        rep = hvd.check_program(quant_step, (x,), world_size=4, config=cfg)
        hits = [f for f in rep.findings if f.code == "HVP109"]
        assert hits and hits[0].severity == "info"
        assert rep.ok
        # eager-only program under the same config: the runtime store owns
        # those residuals (and clear_program_caches zeroes them) -> clean
        def eager_step(x):
            return hvd.allreduce(x)
        assert "HVP109" not in _codes(
            hvd.check_program(eager_step, (x,), world_size=4,
                              config=cfg).findings)

    def test_buffer_reuse_advisory(self, hvd):
        from horovod_tpu.common.config import Config
        x = np.ones((4, 8), np.float32)

        def bad(x):
            a = hvd.allreduce(x)
            b = hvd.allgather(x)      # same buffer again
            return a, b

        def good(x):
            a = hvd.allreduce(x)
            b = hvd.allgather(a)
            return a, b

        cfg = Config()
        cfg.donate_eager = True
        rep = hvd.check_program(bad, (x,), world_size=4, config=cfg)
        reuse = [f for f in rep.findings if f.code == "HVP107"]
        assert reuse and reuse[0].severity == "warning"
        cfg2 = Config()
        rep2 = hvd.check_program(bad, (x,), world_size=4, config=cfg2)
        reuse2 = [f for f in rep2.findings if f.code == "HVP107"]
        assert reuse2 and reuse2[0].severity == "info"
        assert "HVP107" not in _codes(
            hvd.check_program(good, (x,), world_size=4,
                              config=cfg).findings)

    def test_cond_gated_jit_collective(self, hvd):
        mesh = Mesh(np.array(jax.devices()[:4]), ("hvd",))
        x = np.ones((4, 8), np.float32)

        def bad(x):
            def inner(xl):
                return lax.cond(xl.sum() > 0,
                                lambda: lax.psum(xl, "hvd"),
                                lambda: xl * 0)
            return jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"),
                check_vma=False))(x)

        def good(x):
            def inner(xl):
                return lax.psum(xl, "hvd")
            return jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=P("hvd"), out_specs=P()))(x)

        assert "HVP108" in _codes(
            hvd.check_program(bad, (x,), world_size=4).findings)
        assert "HVP108" not in _codes(
            hvd.check_program(good, (x,), world_size=4).findings)

    def test_jit_sequence_extraction(self, hvd):
        """shard_map collectives land in the predicted sequence with the
        canonical op names, in equation order."""
        mesh = Mesh(np.array(jax.devices()[:4]), ("hvd",))
        x = np.ones((4, 8), np.float32)

        def step(x):
            def inner(xl):
                y = lax.psum(xl, "hvd")
                z = lax.ppermute(
                    xl, "hvd", [(0, 1), (1, 2), (2, 3), (3, 0)])
                g = lax.all_gather(xl, "hvd")
                return y + z + jnp.sum(g)
            return jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=P("hvd"),
                out_specs=P("hvd")))(x)

        rep = hvd.check_program(step, (x,), world_size=4)
        ops = [e.op for e in rep.sequences[0]]
        assert ops == ["psum", "ppermute", "all_gather"]
        assert all(e.ps == "axis:hvd" for e in rep.sequences[0])
        assert rep.ok

    def test_kwargs_and_positional_process_set(self, hvd):
        """Interception must resolve operands/sets however they arrive:
        `tensors=` by keyword, process_set positionally on async ops —
        and size stub outputs by the SET, not the world."""
        x = np.ones((2, 8), np.float32)
        ps = hvd.ProcessSet([0, 1])

        def step(x):
            a = hvd.grouped_allreduce(tensors=[x])[0]
            h = hvd.allgather_async(x, ps)          # positional ps
            g = hvd.synchronize(h)
            return a, g

        rep = hvd.check_program(step, (x,), world_size=8)
        events = rep.sequences[0]
        assert [e.op for e in events] == ["allreduce", "allgather"]
        # allreduce rode the global set (leading dim -> world size)...
        assert events[0].shapes[0][0] == 8
        # ...allgather rode the 2-member set: signature over (2, 8) and
        # the stub output scaled by the set size (2*8 columns), which the
        # trace would have crashed on (or mis-signed) had ps been lost.
        assert events[1].shapes[0][0] == 2

    def test_while_loop_collectives_excluded_from_hash(self, hvd):
        """A while-loop body's collectives have no static trip count:
        present in the sequence (repeat=0, diffed for presence) but
        excluded from the exact sequence hash."""
        from horovod_tpu.ops.in_jit import mark_varying
        mesh = Mesh(np.array(jax.devices()[:4]), ("hvd",))
        x = np.ones((4, 8), np.float32)

        def step(x):
            def inner(xl):
                def cond(c):
                    return jnp.sum(c[1]) < 100.0

                def body(c):
                    i, v = c
                    return i + 1, lax.psum(v, "hvd") * 0 \
                        + mark_varying(v, "hvd") + 1.0
                _, out = lax.while_loop(
                    cond, body,
                    (jnp.zeros((), jnp.int32), mark_varying(xl, "hvd")))
                return out
            return jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=P("hvd"),
                out_specs=P("hvd"), check_vma=False))(x)

        rep = hvd.check_program(step, (x,), world_size=4)
        loops = [e for e in rep.sequences[0] if e.repeat == 0]
        assert loops and loops[0].op == "psum"
        # the hash ignores the unknown-count event entirely
        assert rep.sequence_hash(ps="axis:hvd") \
            == an_events.sequence_hash([], ps="axis:hvd")

    def test_sequence_hash_stable_and_rank_invariant(self, hvd):
        x = np.ones((4, 8), np.float32)

        def step(x):
            y = hvd.allreduce(x)
            hvd.barrier()
            return y

        rep = hvd.check_program(step, (x,), world_size=4)
        hashes = {rep.sequence_hash(rank=r) for r in rep.ranks}
        assert len(hashes) == 1
        # deterministic across runs
        rep2 = hvd.check_program(step, (x,), world_size=4)
        assert rep2.sequence_hash() == rep.sequence_hash()

    def test_large_world_sampled(self, hvd):
        x = np.ones((4, 8), np.float32)

        def step(x):
            if hvd.rank() == hvd.size() - 1:
                hvd.barrier()       # last-rank-only: must still be caught
            return hvd.allreduce(x)

        rep = hvd.check_program(step, (x,), world_size=1024)
        assert rep.sampled
        assert not rep.ok
        assert any(f.code == "HVP101" for f in rep.findings)

    def test_single_process_cross_check(self, hvd):
        """Predicted identity tuples match the flight recorder's on a real
        (single-process, 8-virtual-rank) run — per-event (op, ps, seq,
        sig) and the whole-sequence hash."""
        from horovod_tpu.analysis import cross_check
        from horovod_tpu.flight import recorder

        n = hvd.size()
        x = np.ones((n, 4), np.float32)
        z = np.ones((n, 2, 3), np.float32)

        def step(x, z):
            a = hvd.allreduce(x)
            b = hvd.allgather(z)
            c = hvd.allreduce(x * 2.0)
            hvd.barrier()
            return a, b, c

        rep = hvd.check_program(step, (x, z), world_size=n)
        assert rep.ok
        # Fresh ring: the session-scoped singleton's per-set seq counter
        # is cumulative across earlier tests, while a run's prediction
        # starts at seq 1.
        prev_ring, prev_armed = recorder._recorder, recorder.armed
        recorder._recorder = recorder.FlightRecorder(capacity=64)
        recorder.set_enabled(True)
        try:
            step(x, z)
            ev = recorder.events()
        finally:
            recorder._recorder, recorder.armed = prev_ring, prev_armed
        res = cross_check(rep, ev)
        assert res["match"], res
        assert res["predicted_hash"] == res["recorded_hash"]
        assert res["n_predicted"] == 4


def _xcheck_job():
    """Worker side of the multi-process cross-check: run a short eager
    program for real, return the flight ring's dispatch identities."""
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.flight import recorder

    recorder.set_enabled(True)
    nl = len(hvd.topology().local_device_ranks)
    x = np.ones((nl, 6), np.float32)
    z = np.ones((nl, 3, 2), np.float32)
    before = recorder.get().appended()
    hvd.allreduce(x, op=hvd.Sum)
    hvd.allgather(z)
    hvd.allreduce(x, op=hvd.Sum)
    ev = [e for e in recorder.events()
          if e["i"] >= before and e.get("kind") == "dispatch"]
    return (hvd.cross_rank(), hvd.size(), ev)


class TestMultiprocCrossCheck:
    @pytest.mark.slow
    def test_predicted_matches_recorded(self, hvd, shared_cluster):
        """The analyzer's predicted collective sequence hash matches the
        flight recorder's recorded sequence on a real 2-process CPU-tier
        run — every (op, ps, seq, sig) identity lines up."""
        results = shared_cluster("localhost:1,127.0.0.1:1",
                                 extra_env={"HVD_XCHECK": "1"}).run(
            _xcheck_job)
        assert len(results) == 2
        world = results[0][1]

        def step(x, z):
            hvd.allreduce(x, op=hvd.Sum)
            hvd.allgather(z)
            hvd.allreduce(x, op=hvd.Sum)

        # what each worker passed locally: one row per local rank
        nl = world // 2
        x = np.ones((nl, 6), np.float32)
        z = np.ones((nl, 3, 2), np.float32)
        rep = hvd.check_program(step, (x, z), world_size=world)
        assert rep.ok
        predicted_hash = rep.sequence_hash(ps="global")
        for rank, _, ev in results:
            recorded_hash = an_events.sequence_hash(ev, ps="global")
            assert recorded_hash == predicted_hash, (rank, ev)
            assert [(e["op"], e["ps"], e["seq"], e["sig"]) for e in ev] \
                == rep.predicted(rank=0)


# ---------------------------------------------------------------------------
# hvdcost: the static per-link-tier cost model (analysis/cost.py)
# ---------------------------------------------------------------------------


class TestHierarchicalExchangeShape:
    """check_program recognition of the hierarchical 2-level exchange
    (local RS -> cross -> local AG), the HVP113 1-slice advisory, and the
    HVP106 suppression for a block-scaled cross leg — pos/neg corpus."""

    @staticmethod
    def _torus_step(cross_wire):
        from horovod_tpu.parallel.strategies import allreduce_torus
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                    ("cross", "local"))

        def step(x):
            def inner(xl):
                return allreduce_torus(
                    xl.reshape(-1),
                    cross_compression=cross_wire).reshape(xl.shape)
            return jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=P(("cross", "local")),
                out_specs=P(("cross", "local")), check_vma=False))(x)

        return step

    def test_triads_recognized_with_quantized_flag(self, hvd):
        from horovod_tpu.analysis.program import hier_triads
        x = np.ones((8, 2 * 8 * 1024), np.float32)
        rep = hvd.check_program(self._torus_step("int8"), (x,),
                                world_size=8)
        triads = hier_triads(rep.sequences[rep.ranks[0]])
        assert len(triads) == 1
        assert triads[0]["quantized"]
        rep_exact = hvd.check_program(self._torus_step(None), (x,),
                                      world_size=8)
        triads = hier_triads(rep_exact.sequences[rep_exact.ranks[0]])
        assert len(triads) == 1
        assert not triads[0]["quantized"]

    def test_hvp113_hierarchical_over_one_slice(self, hvd, monkeypatch):
        monkeypatch.delenv("HOROVOD_MESH_SLICES", raising=False)
        x = np.ones((8, 2 * 8 * 1024), np.float32)
        rep = hvd.check_program(self._torus_step(None), (x,),
                                world_size=8)
        assert "HVP113" in _codes(rep.findings)
        assert rep.ok          # advisory only

    def test_hvp113_clean_on_multislice_layout(self, hvd, monkeypatch):
        monkeypatch.setenv("HOROVOD_MESH_SLICES", "2")
        x = np.ones((8, 2 * 8 * 1024), np.float32)
        rep = hvd.check_program(self._torus_step(None), (x,),
                                world_size=8)
        assert "HVP113" not in _codes(rep.findings)

    def test_hvp113_armed_dispatch_tier_on_one_slice(self, hvd,
                                                     monkeypatch):
        """The eager side: HOROVOD_HIERARCHICAL_DISPATCH configured over
        a 1-slice layout is inert pure-overhead config — advisory."""
        from horovod_tpu.common.config import Config
        monkeypatch.delenv("HOROVOD_MESH_SLICES", raising=False)
        x = np.ones((8, 8 * 1024), np.float32)

        def step(x):
            return hvd.allreduce(x, op=hvd.Sum)

        cfg = Config(hierarchical_dispatch=True)
        assert "HVP113" in _codes(
            hvd.check_program(step, (x,), world_size=8,
                              config=cfg).findings)
        assert "HVP113" not in _codes(
            hvd.check_program(step, (x,), world_size=8,
                              config=Config()).findings)

    def test_hvp106_cross_policy(self, hvd, monkeypatch):
        """HVP106 fires for a configured DCN wire policy that the jit
        program ignores (flat fp32 psum), names the wire_dtype_dcn knob —
        and is suppressed when the program's cross leg IS block-scaled."""
        from horovod_tpu.common.config import Config
        monkeypatch.setenv("HOROVOD_MESH_SLICES", "2")
        mesh = Mesh(np.array(jax.devices()[:8]), ("hvd",))
        x = np.ones((8, 2 * 8 * 1024), np.float32)

        def flat_step(x):
            def inner(xl):
                return lax.psum(xl, "hvd")
            return jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=P("hvd"), out_specs=P()))(x)

        cfg = Config(wire_dtype_dcn="int8")
        cfg.wire_error_feedback = False
        findings = hvd.check_program(flat_step, (x,), world_size=8,
                                     config=cfg).findings
        assert "HVP106" in _codes(findings)
        assert any("wire_dtype_dcn" in f.message for f in findings
                   if f.code == "HVP106")
        # quantized cross leg -> the fp32 local legs are the tier's
        # deliberate ICI policy, not a missed wire
        assert "HVP106" not in _codes(
            hvd.check_program(self._torus_step("int8"), (x,),
                              world_size=8, config=cfg).findings)


class TestA2AHierarchyLint:
    """ISSUE 18 corpus: HVP113 extended to the armed hierarchical
    ALLTOALL tier over a 1-slice layout, and HVP106 extended to the
    expert cross-dtype knob with the block-scaled a2a suppression."""

    def test_hvp113_a2a_armed_over_one_slice(self, hvd, monkeypatch):
        from horovod_tpu.common.config import Config
        monkeypatch.delenv("HOROVOD_MESH_SLICES", raising=False)
        x = np.ones((8, 8 * 64), np.float32)

        def step(x):
            return hvd.alltoall(x)

        cfg = Config(hierarchical_alltoall=True)
        rep = hvd.check_program(step, (x,), world_size=8, config=cfg)
        assert "HVP113" in _codes(rep.findings)
        assert rep.ok                         # advisory only
        assert any(f.op == "alltoall" for f in rep.findings
                   if f.code == "HVP113")
        # knob off -> clean
        assert "HVP113" not in _codes(
            hvd.check_program(step, (x,), world_size=8,
                              config=Config()).findings)

    def test_hvp113_a2a_clean_on_multislice_layout(self, hvd,
                                                   monkeypatch):
        from horovod_tpu.common.config import Config
        monkeypatch.setenv("HOROVOD_MESH_SLICES", "2")
        x = np.ones((8, 8 * 64), np.float32)

        def step(x):
            return hvd.alltoall(x)

        assert "HVP113" not in _codes(
            hvd.check_program(step, (x,), world_size=8,
                              config=Config(
                                  hierarchical_alltoall=True)).findings)

    def test_hvp113_a2a_registry_pin_counts_as_armed(self, hvd,
                                                     monkeypatch):
        """The registry pin (hvd.set_alltoall_strategy) arms the tier
        exactly like the knob — a pinned 1-slice job gets the same
        advisory."""
        from horovod_tpu.common.config import Config
        from horovod_tpu.ops import wire as _wire
        monkeypatch.delenv("HOROVOD_MESH_SLICES", raising=False)
        x = np.ones((8, 8 * 64), np.float32)

        def step(x):
            return hvd.alltoall(x)

        _wire.set_alltoall_strategy("hier")
        try:
            assert "HVP113" in _codes(
                hvd.check_program(step, (x,), world_size=8,
                                  config=Config()).findings)
        finally:
            _wire.clear_strategy_registry()

    def test_hvp106_names_a2a_cross_knob(self, hvd, monkeypatch):
        """An armed HOROVOD_ALLTOALL_CROSS_DTYPE that the jit program
        ignores (flat fp32 psum) is a missed wire — the advisory names
        the a2a knob; a program whose expert cross leg IS block-scaled
        (strategies.alltoall_tiered int8) suppresses it."""
        from horovod_tpu.common.config import Config
        from horovod_tpu.parallel.strategies import alltoall_tiered
        monkeypatch.setenv("HOROVOD_MESH_SLICES", "2")
        mesh = Mesh(np.array(jax.devices()[:8]), ("hvd",))
        x = np.ones((8, 2 * 8 * 1024), np.float32)

        def flat_step(x):
            def inner(xl):
                return lax.psum(xl, "hvd")
            return jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=P("hvd"), out_specs=P()))(x)

        cfg = Config(alltoall_cross_dtype="int8")
        cfg.wire_error_feedback = False
        findings = hvd.check_program(flat_step, (x,), world_size=8,
                                     config=cfg).findings
        assert "HVP106" in _codes(findings)
        assert any("alltoall_cross_dtype" in f.message for f in findings
                   if f.code == "HVP106")

        hmesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                     ("cross", "local"))
        xa = np.ones((8 * 8, 2048), np.float32)   # shard (8, 2048)

        def tiered_step(x):
            def inner(xl):
                return alltoall_tiered(xl, cross_wire="int8")
            return jax.jit(jax.shard_map(
                inner, mesh=hmesh, in_specs=P(("cross", "local")),
                out_specs=P(("cross", "local")), check_vma=False))(x)

        assert "HVP106" not in _codes(
            hvd.check_program(tiered_step, (xa,), world_size=8,
                              config=cfg).findings)


class TestCostModel:
    def test_tier_split_flat_allreduce(self, hvd):
        """fp32 allreduce over the global set: total = 2x global bytes
        (the runtime's RS+AG accounting), DCN share = S/n of each ring
        leg; single-slice worlds put everything on ICI."""
        from horovod_tpu.analysis import cost as an_cost

        n = 8
        x = np.ones((n, 64), np.float32)

        def step(x):
            return hvd.allreduce(x, op=hvd.Sum)

        rep = hvd.check_program(step, (x,), world_size=n)
        cr = an_cost.cost_report(rep, num_slices=2)
        total = 2 * x.nbytes
        row = cr.rows[0]
        assert row.dtype == "float32"
        assert row.total_bytes == total
        assert row.dcn_bytes == int(round(total * 2 / n))
        assert cr.bytes_by_tier["ici"] + cr.bytes_by_tier["dcn"] == total
        # single slice: all ICI
        cr1 = an_cost.cost_report(rep, num_slices=1)
        assert cr1.bytes_by_tier == {"ici": total, "dcn": 0}
        # non-divisible slice count collapses to single-slice (the mesh
        # construction's own rule)
        cr3 = an_cost.cost_report(rep, num_slices=3)
        assert cr3.num_slices == 1 and cr3.bytes_by_tier["dcn"] == 0

    def test_control_plane_rpcs_priced_per_tier(self, hvd):
        """ISSUE 14: the static model prices negotiation RPCs alongside
        wire bytes — a dynamic-shape alltoall costs one round, whose
        per-role gets follow control_plane.exchange_plan under the
        resolved hierarchy (member O(1), leader slice_size-1 +
        num_slices-1), vs the flat O(world) fan-out."""
        from horovod_tpu.analysis import cost as an_cost

        n = 8
        x = np.ones((n, n), np.float32)
        splits = np.ones((n, n), int)

        def step(x):
            return hvd.alltoall(x, splits=splits)[0]

        rep = hvd.check_program(step, (x,), world_size=n)
        cr = an_cost.cost_report(rep, num_slices=2)
        cp = cr.control_plane
        assert cp["strategy"] == "hier"
        assert cp["rounds_per_step"] == 1
        assert cp["member_gets"] == 1
        assert cp["leader_gets"] == (4 - 1) + (2 - 1)
        assert cp["flat_gets"] == n - 1
        assert cr.to_dict()["control_plane"] == cp
        assert "control plane (hier)" in cr.render()
        # Single-slice layout: the flat plan, priced at O(world).
        cp1 = an_cost.cost_report(rep, num_slices=1).control_plane
        assert cp1["strategy"] == "flat"
        assert cp1["member_gets"] == n - 1 == cp1["leader_gets"]

    def test_quantized_exchange_split_and_dtype_totals(self, hvd):
        """int8 wire: bytes = the exchange's exact accounting (1-byte
        legs + scales + padding); first leg priced as all-to-all
        (1 - L/n cross), second as ring (S/n cross). Small fp32
        collectives stay exact; per-dtype totals equal the tier sum."""
        from horovod_tpu.analysis import cost as an_cost
        from horovod_tpu.common.config import Config
        from horovod_tpu.ops import wire

        n = 8
        g = np.ones((n, 64 * 1024), np.float32)
        s = np.ones((n, 8), np.float32)
        m = np.ones((n, 8), np.float32)

        def step(g, s, m):
            a = hvd.allreduce(g, op=hvd.Sum)
            b = hvd.allreduce(s)
            c = hvd.allgather(m)
            hvd.barrier()
            return a, b, c

        cfg = Config(wire_dtype="int8")
        rep = hvd.check_program(step, (g, s, m), world_size=n, config=cfg)
        cr = an_cost.cost_report(rep, config=cfg, num_slices=2)
        leg = wire.exchange_leg_bytes(64 * 1024, n)
        assert cr.bytes_by_dtype["int8"] == 2 * leg \
            == wire.exchange_wire_bytes(64 * 1024, n)
        q = [r for r in cr.rows if r.dtype == "int8"][0]
        # a2a leg: 1 - 4/8 = 0.5 cross; ring leg: 2/8 = 0.25 cross
        assert q.dcn_bytes == int(round(leg * 0.5)) + int(round(leg * 0.25))
        assert cr.bytes_by_dtype["float32"] == 2 * s.nbytes + m.nbytes
        assert sum(cr.bytes_by_tier.values()) \
            == sum(cr.bytes_by_dtype.values())
        # the hierarchical what-if moves the allreduce's DCN below flat
        assert cr.hierarchical["dcn"] < cr.bytes_by_tier["dcn"]
        assert cr.time_estimate["bound"] in ("ici", "dcn")

    def test_runtime_refused_wires_stay_exact(self, hvd):
        """The static eligibility gate mirrors the dispatch layer: a Min
        reduction and a sub-block payload keep the exact fp32 wire even
        with int8 configured (wire.quantized_eligible is THE shared
        predicate)."""
        from horovod_tpu.analysis import cost as an_cost
        from horovod_tpu.common.config import Config

        n = 8
        big = np.ones((n, 64 * 1024), np.float32)
        tiny = np.ones((n, 16), np.float32)

        def step(big, tiny):
            a = hvd.allreduce(big, op=hvd.Min)      # non-Sum/Average
            b = hvd.allreduce(tiny, op=hvd.Sum)     # < 1 block/rank
            return a, b

        cfg = Config(wire_dtype="int8")
        rep = hvd.check_program(step, (big, tiny), world_size=n,
                                config=cfg)
        cr = an_cost.cost_report(rep, config=cfg, num_slices=2)
        assert "int8" not in cr.bytes_by_dtype
        assert cr.bytes_by_dtype["float32"] \
            == 2 * big.nbytes + 2 * tiny.nbytes

    def test_use_registry_false_ignores_wire_pins(self, hvd):
        """Counterfactual pricing: an explicit hvd.set_wire_dtype pin
        steers the default cost model (it steers the runtime), but
        use_registry=False prices against the given config alone — the
        bench's static_cost record regression (a leftover '' pin from
        the sweep silently priced the int8 leg as fp32)."""
        from horovod_tpu.analysis import cost as an_cost
        from horovod_tpu.common.config import Config
        from horovod_tpu.ops import wire

        n = 8
        x = np.ones((n, 64 * 1024), np.float32)

        def step(x):
            return hvd.allreduce(x, op=hvd.Sum)

        cfg = Config(wire_dtype="int8")
        rep = hvd.check_program(step, (x,), world_size=n, config=cfg)
        hvd.set_wire_dtype("")           # user pin: full precision
        try:
            pinned = an_cost.cost_report(rep, config=cfg, num_slices=1)
            counterfactual = an_cost.cost_report(
                rep, config=cfg, num_slices=1, use_registry=False)
        finally:
            wire.clear_wire_registry()
        assert "int8" not in pinned.bytes_by_dtype          # pin wins
        assert "int8" in counterfactual.bytes_by_dtype      # config wins

    def test_jit_axis_tier_classification(self, hvd):
        """A psum over the DCN mesh's `cross` axis is pure DCN; over
        `local` pure ICI; a world-spanning axis mixes at S/n."""
        from horovod_tpu.analysis import cost as an_cost

        devs = np.array(jax.devices()[:8]).reshape(2, 4)
        mesh = Mesh(devs, ("cross", "local"))
        x = np.ones((8, 16), np.float32)

        def cross_step(x):
            def inner(xl):
                return lax.psum(xl, "cross")
            return jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=P("cross"), out_specs=P(),
                check_vma=False))(x)

        def local_step(x):
            def inner(xl):
                return lax.psum(xl, "local")
            return jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=P(None, "local"),
                out_specs=P(None), check_vma=False))(x)

        repc = hvd.check_program(cross_step, (x,), world_size=8)
        crc = an_cost.cost_report(repc, num_slices=2)
        assert crc.bytes_by_tier["ici"] == 0
        assert crc.bytes_by_tier["dcn"] > 0
        assert crc.jit_bytes_by_dtype and not crc.bytes_by_dtype

        repl = hvd.check_program(local_step, (x,), world_size=8)
        crl = an_cost.cost_report(repl, num_slices=2)
        assert crl.bytes_by_tier["dcn"] == 0
        assert crl.bytes_by_tier["ici"] > 0

    def test_dcn_budget_hvp111(self, hvd):
        from horovod_tpu.analysis import cost as an_cost

        n = 8
        x = np.ones((n, 64 * 1024), np.float32)

        def step(x):
            return hvd.allreduce(x, op=hvd.Sum)

        rep = hvd.check_program(step, (x,), world_size=n)
        cr = an_cost.cost_report(rep, num_slices=2, dcn_budget_bytes=100)
        assert not cr.ok
        hit = [f for f in cr.findings if f.code == "HVP111"]
        assert hit and hit[0].severity == "error"
        assert "EXCEEDED" in cr.render()
        ok = an_cost.cost_report(rep, num_slices=2,
                                 dcn_budget_bytes=10**12)
        assert ok.ok and "OK" in ok.render()


class TestUnboundedRepeatCost:
    def test_hvp112_and_lower_bound_totals(self, hvd):
        """Satellite: a while-wrapped psum must raise HVP112 and flag the
        cost totals as LOWER BOUNDS (counted once), not exact."""
        from horovod_tpu.analysis import cost as an_cost
        from horovod_tpu.ops.in_jit import mark_varying

        mesh = Mesh(np.array(jax.devices()[:4]), ("hvd",))
        x = np.ones((4, 8), np.float32)

        def step(x):
            def inner(xl):
                def cond(c):
                    return jnp.sum(c[1]) < 100.0

                def body(c):
                    i, v = c
                    return i + 1, lax.psum(v, "hvd") * 0 \
                        + mark_varying(v, "hvd") + 1.0
                _, out = lax.while_loop(
                    cond, body,
                    (jnp.zeros((), jnp.int32), mark_varying(xl, "hvd")))
                return out
            return jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=P("hvd"),
                out_specs=P("hvd"), check_vma=False))(x)

        rep = hvd.check_program(step, (x,), world_size=4)
        cr = an_cost.cost_report(rep, num_slices=2)
        hits = [f for f in cr.findings if f.code == "HVP112"]
        assert hits and hits[0].severity == "info" and cr.ok
        assert not cr.exact
        assert "lower bound" in cr.render()
        # the while-body psum is priced exactly once
        loops = [r for r in cr.rows if r.repeat == 0]
        assert loops and loops[0].total_bytes == loops[0].wire_bytes
        # the elastic checker marks the same limitation
        er = hvd.check_elastic(step, (x,), worlds=(4, 2))
        assert any(f.code == "HVP112" for f in er.findings)
        assert er.ok     # advisory only


class TestCrossCheckBytes:
    def test_fused_quantized_step_within_5pct(self, hvd):
        """Acceptance: the static bytes_by_tier prediction for a
        representative fused+quantized step matches the runtime
        wire_bytes_total{dtype} counters within 5% (exact in practice) on
        a live 8-virtual-rank run."""
        from horovod_tpu.analysis import cost as an_cost
        from horovod_tpu.ops import fusion, wire

        n = hvd.size()
        g = np.ones((n, 32 * 1024), np.float32)   # quantized-eligible
        s = np.ones((n, 16), np.float32)

        # `sync` materializes the fused result BEFORE the next collective
        # at runtime (np.asarray) — the cycle-thread flush executing
        # concurrently with a later eager program deadlocks the
        # in-process CPU rendezvous (the cross-program flavor of the
        # conftest XLA_FLAGS note). Under check_program it stays the
        # identity: the traced step must not materialize tracers.
        def step(g, s, sync=lambda x: x):
            h = hvd.allreduce_async(g, op=hvd.Sum, name="fused_q")
            fused = sync(hvd.synchronize(h))
            a = hvd.allreduce(g, op=hvd.Sum, name="eager_q")
            b = hvd.allgather(s)
            return fused, a, b

        rt = fusion.get_runtime()
        prev_rt = rt.wire_dtype
        hvd.set_wire_dtype("int8")
        rt.wire_dtype = jnp.int8
        try:
            step(g, s, sync=np.asarray)    # warm: compiles + plans
            base = hvd.metrics_snapshot()
            iters = 3
            for _ in range(iters):
                step(g, s, sync=np.asarray)
            after = hvd.metrics_snapshot()
            rep = hvd.check_program(step, (g, s), world_size=n)
            cost = an_cost.cost_report(rep, num_slices=2)
            res = an_cost.cross_check_bytes(cost, after, base, steps=iters)
        finally:
            rt.wire_dtype = prev_rt
            wire.clear_wire_registry()
            wire.reset_error_feedback()
        assert set(cost.bytes_by_dtype) == {"int8", "float32"}
        assert res["match"], res
        for d in res["per_dtype"].values():
            assert abs(d["delta"]) <= 0.05 * max(d["predicted"], 1.0), res
        assert cost.bytes_by_tier["ici"] > 0
        assert cost.bytes_by_tier["dcn"] > 0


def _cost_xcheck_job():
    """Worker side of the multi-process cost cross-check: run the
    fused+quantized step for real under HOROVOD_MESH_SLICES=2 and return
    the wire counter snapshots around a measured window."""
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.ops import wire

    n = hvd.size()
    nl = len(hvd.topology().local_device_ranks)
    g = np.ones((nl, 32 * 1024), np.float32)
    s = np.ones((nl, 16), np.float32)
    hvd.set_wire_dtype("int8")

    def step():
        a = hvd.allreduce(g, op=hvd.Sum)
        b = hvd.allgather(s)
        return a, b

    try:
        step()
        base = hvd.metrics_snapshot()
        iters = 3
        for _ in range(iters):
            step()
        after = hvd.metrics_snapshot()
    finally:
        wire.clear_wire_registry()
        wire.reset_error_feedback()
    slices = hvd.topology().num_slices
    return (hvd.cross_rank(), n, slices, iters, base, after)


class TestMultiprocCostCrossCheck:
    @pytest.mark.slow
    def test_static_prediction_matches_cluster_counters(
            self, hvd, shared_cluster):
        """Acceptance: CPU-tier MULTI-PROCESS run with
        HOROVOD_MESH_SLICES=2 — every worker's measured
        wire_bytes_total{dtype} deltas match the static per-dtype
        prediction within 5%."""
        from horovod_tpu.analysis import cost as an_cost

        # HOROVOD_MESH_SLICES both forces the DCN hierarchy under test
        # and keys this cluster separately from the other cross-check's.
        results = shared_cluster(
            "localhost:1,127.0.0.1:1",
            extra_env={"HOROVOD_MESH_SLICES": "2"}).run(_cost_xcheck_job)
        assert len(results) == 2
        world = results[0][1]
        assert results[0][2] == 2          # the forced DCN hierarchy took
        nl = world // 2
        g = np.ones((nl, 32 * 1024), np.float32)
        s = np.ones((nl, 16), np.float32)

        def step(g, s):
            a = hvd.allreduce(g, op=hvd.Sum)
            b = hvd.allgather(s)
            return a, b

        from horovod_tpu.common.config import Config
        cfg = Config(wire_dtype="int8")
        rep = hvd.check_program(step, (g, s), world_size=world, config=cfg)
        cost = an_cost.cost_report(rep, config=cfg, num_slices=2)
        assert cost.bytes_by_tier["dcn"] > 0
        for _, _, _, iters, base, after in results:
            res = an_cost.cross_check_bytes(cost, after, base, steps=iters)
            assert res["match"], res


# ---------------------------------------------------------------------------
# Elastic world-transition model checker (check_elastic, HVP110)
# ---------------------------------------------------------------------------


class TestElasticChecker:
    def test_zero_reshard_scenario_passes_clean(self, hvd):
        """The known-good elastic step: ZeRO-1 state resharded per
        generation (the tests/test_elastic_reshard.py scenario — per-rank
        moment shards are ceil(B/n), grads replicated) stays stream-
        coherent across the chaos soaks' shrink/grow ladder."""
        logical = 12 + 5                  # the reshard test's param count

        def step(moment_shard, grads):
            g = hvd.allreduce(grads, op=hvd.Sum)
            full = hvd.allgather(moment_shard)
            return g, full

        def args_for(w):
            shard = (logical + (-logical) % w) // w
            return (np.zeros((w, shard), np.float32),
                    np.zeros((w, logical), np.float32))

        rep = hvd.check_elastic(step, worlds=(8, 7, 4, 8),
                                args_for=args_for)
        assert rep.ok, rep.render()
        assert not rep.findings
        assert set(rep.reports) == {8, 7, 4}
        assert "safe to resize" in rep.render()

    def test_world_gated_collective_hvp110(self, hvd):
        """Known-bad corpus: a collective only dispatched at some world
        sizes — the resized generation replays against mismatched
        peers."""
        def step(x):
            a = hvd.allreduce(x, op=hvd.Sum)
            if hvd.size() >= 8:
                a = a + hvd.allreduce(x * 2, op=hvd.Sum)
            return a

        rep = hvd.check_elastic(
            step, worlds=(8, 7, 4, 8),
            args_for=lambda w: (np.zeros((w, 128), np.float32),))
        assert not rep.ok
        hits = [f for f in rep.findings if f.code == "HVP110"]
        assert hits and hits[0].severity == "error"
        assert "world" in hits[0].message

    def test_world_dependent_payload_hvp110(self, hvd):
        """Known-bad corpus: a per-rank payload that tracks world size
        without being an even reshard of one logical buffer (seeded
        world-size-dependent signature)."""
        def step(x):
            return hvd.allreduce(x, op=hvd.Sum)

        rep = hvd.check_elastic(
            step, worlds=(8, 4),
            args_for=lambda w: (np.zeros((w, w * 16), np.float32),))
        assert not rep.ok
        assert any(f.code == "HVP110" and "signature" in f.message
                   for f in rep.findings)

    def test_world_dependent_dtype_hvp110(self, hvd):
        def step(x):
            y = x.astype(jnp.bfloat16) if hvd.size() > 4 else x
            return hvd.allreduce(y, op=hvd.Sum)

        rep = hvd.check_elastic(
            step, worlds=(8, 4),
            args_for=lambda w: (np.zeros((w, 256), np.float32),))
        assert not rep.ok
        assert any(f.code == "HVP110" and "moves" in f.message
                   for f in rep.findings)

    def test_per_world_errors_propagate(self, hvd):
        """A rank-gated collective (HVP101) at any single generation
        makes the elastic report not-ok even when the generations agree
        with each other."""
        def step(x):
            if hvd.rank() == 0:
                hvd.barrier()
            return hvd.allreduce(x)

        rep = hvd.check_elastic(
            step, worlds=(4, 2),
            args_for=lambda w: (np.zeros((w, 8), np.float32),))
        assert not rep.ok
        assert any(f.code == "HVP101" for f in rep.errors())


class TestSamplingMidRank:
    def test_mid_neighbor_rank_gate_caught(self, hvd):
        """Satellite: worlds >16 sample boundary ranks only — a
        collective gated on size//2 + 1 escaped HVP101 before the mid
        neighborhood (mid-1, mid, mid+1) joined the sampled set."""
        x = np.ones((4, 8), np.float32)

        def step(x):
            if hvd.rank() == hvd.size() // 2 + 1:
                hvd.barrier()        # mid+1-only: must still be caught
            return hvd.allreduce(x)

        rep = hvd.check_program(step, (x,), world_size=1024)
        assert rep.sampled
        assert not rep.ok
        assert any(f.code == "HVP101" for f in rep.findings)
        mid = 1024 // 2
        assert {mid - 1, mid, mid + 1} <= set(rep.ranks)


# ---------------------------------------------------------------------------
# The cost CLI / CI gate (python -m horovod_tpu.analysis.cost)
# ---------------------------------------------------------------------------


class TestCostCLI:
    def _run(self, *extra):
        import subprocess

        env = dict(os.environ, PYTHONPATH=_REPO)
        return subprocess.run(
            [sys.executable, "-m", "horovod_tpu.analysis.cost",
             "--world", "8", "--slices", "2", "--wire", "int8",
             "--payload-kb", "256", *extra],
            capture_output=True, text=True, env=env, cwd=_REPO)

    def test_clean_run_exits_zero_within_budget(self):
        t0 = time.monotonic()
        r = self._run("--elastic", "8,7,4,8")
        dt = time.monotonic() - t0
        assert r.returncode == 0, r.stdout + r.stderr
        assert "bytes_by_tier" in r.stdout
        assert "hvdcost: OK" in r.stdout
        assert "safe to resize" in r.stdout
        assert dt < 30.0, f"cost CLI took {dt:.1f}s (budget 30s)"

    def test_budget_violation_exits_one(self):
        r = self._run("--dcn-budget", "1000")
        assert r.returncode == 1, r.stdout + r.stderr
        assert "HVP111" in r.stdout

    def test_json_output_parses(self):
        import json as _json

        r = self._run("--json")
        assert r.returncode == 0, r.stdout + r.stderr
        out = _json.loads(r.stdout)
        assert out["cost"]["bytes_by_tier"]["dcn"] > 0
        assert out["cost"]["ok"] and out["check"]["ok"]

    def test_lint_cost_mode_runs_both_gates(self):
        """scripts/lint.py --cost: one command, both static gates."""
        import subprocess

        env = dict(os.environ, PYTHONPATH=_REPO)
        r = subprocess.run(
            [sys.executable, os.path.join(_REPO, "scripts", "lint.py"),
             "--cost", "--cost-args", "--world", "4", "--payload-kb",
             "64"],
            capture_output=True, text=True, env=env, cwd=_REPO)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "hvdcost: OK" in r.stdout


# ---------------------------------------------------------------------------
# Orphan reaper (scripts/reap_workers.py + the conftest session hook)
# ---------------------------------------------------------------------------


def _load_reaper():
    import importlib.util

    path = os.path.join(_REPO, "scripts", "reap_workers.py")
    spec = importlib.util.spec_from_file_location("_reap_test_mod", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestReapWorkers:
    def test_finds_and_kills_matching_process(self):
        """A decoy process carrying the marker in its argv is found by
        pattern, skipped by the orphans-only default (its parent — us —
        is alive), and killed by the explicit reap."""
        import subprocess

        reaper = _load_reaper()
        marker = "hvd_reap_selftest_marker"
        proc = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(120)",
             marker],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        try:
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if proc.pid in reaper.find_workers(marker,
                                                   orphans_only=False):
                    break
                time.sleep(0.05)
            assert proc.pid in reaper.find_workers(marker,
                                                   orphans_only=False)
            # alive parent -> NOT an orphan -> the session-start default
            # must never touch it
            assert proc.pid not in reaper.find_workers(marker,
                                                       orphans_only=True)
            reaped = reaper.reap(pattern=marker, orphans_only=False,
                                 grace_s=3.0)
            assert proc.pid in reaped
            assert proc.wait(timeout=10) is not None
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)

    def test_dry_run_kills_nothing(self):
        import subprocess

        reaper = _load_reaper()
        marker = "hvd_reap_selftest_dry"
        proc = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(120)",
             marker],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        try:
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if proc.pid in reaper.find_workers(marker,
                                                   orphans_only=False):
                    break
                time.sleep(0.05)
            listed = reaper.reap(pattern=marker, orphans_only=False,
                                 dry_run=True)
            assert proc.pid in listed
            assert proc.poll() is None       # still alive
        finally:
            proc.kill()
            proc.wait(timeout=10)

    def test_never_reaps_itself(self):
        reaper = _load_reaper()
        # our own cmdline contains whatever pytest was invoked with; use
        # a pattern guaranteed to match this process
        import os as _os
        assert _os.getpid() not in reaper.find_workers(
            "python", orphans_only=False)


# ---------------------------------------------------------------------------
# AST lint corpus: each rule class, positive + negative
# ---------------------------------------------------------------------------

_DECLARED = declared_knobs()


def _lint(src, rel="horovod_tpu/ops/x.py"):
    return lint_source(src, rel_path=rel, declared=_DECLARED)


class TestLintRules:
    def test_hvl001_hvl006_retired_in_favor_of_hvdrace(self):
        """Lock-discipline linting moved to the call-graph-aware hvdrace
        (HVR202 sees holds across function boundaries; the old per-with
        HVL001/HVL006 could not).  hvdlint no longer emits either code —
        the same patterns now land as HVR202 (TestRaceRules)."""
        held_blocking = (
            "def flush(self):\n"
            "    with self._lock:\n"
            "        self.client.allreduce(x)\n")
        held_sleep = ("import time\n"
                      "with self._lock:\n"
                      "    time.sleep(0.1)\n")
        assert not _lint(held_blocking)
        assert not _lint(held_sleep)
        from horovod_tpu.analysis.lint import _DEFAULT_RULES
        assert "HVL001" not in _DEFAULT_RULES
        assert "HVL006" not in _DEFAULT_RULES

    def test_hvl002_undeclared_env_read(self):
        bad = "import os\nv = os.environ.get('HOROVOD_NOT_A_KNOB')\n"
        good = "import os\nv = os.environ.get('HOROVOD_FUSION_THRESHOLD')\n"
        bootstrap = "import os\nv = os.environ.get('HOROVOD_KV_ADDR')\n"
        helper = "v = _env_int('HOROVOD_ALSO_NOT_A_KNOB', 3)\n"
        subscript = "import os\nv = os.environ['HOROVOD_SOME_KNOB']\n"
        assert {"HVL002"} == _codes(_lint(bad))
        assert not _lint(good)
        assert not _lint(bootstrap)
        assert {"HVL002"} == _codes(_lint(helper))
        assert {"HVL002"} == _codes(_lint(subscript))
        assert not _lint(
            "import os\nv = os.environ['HOROVOD_KV_PORT']\n")

    def test_hvl003_ambient_env_write(self):
        bad = "import os\nos.environ['HOROVOD_FUSION_THRESHOLD'] = '1'\n"
        assert {"HVL003"} == _codes(_lint(bad))
        # launcher layer is allowed to export worker env
        assert not _lint(bad, rel="horovod_tpu/runner/launch.py")
        # non-knob env writes are out of scope
        assert not _lint("import os\nos.environ['PATH'] = 'x'\n")

    def test_hvl004_rank_conditional_collective(self):
        bad = (
            "def main():\n"
            "    if hvd.rank() == 0:\n"
            "        hvd.broadcast_object(state)\n")
        good = (
            "def main():\n"
            "    if hvd.rank() == 0:\n"
            "        print('saving checkpoint')\n"
            "    hvd.broadcast_object(state)\n")
        assert {"HVL004"} == _codes(_lint(bad, rel="examples/train.py"))
        assert not _lint(good, rel="examples/train.py")
        # library internals legitimately rank-branch (mirror dispatch)
        assert "HVL004" not in _codes(
            _lint(bad, rel="horovod_tpu/ops/collective_ops.py"))

    def test_hvl005_non_daemon_thread(self):
        bad = ("import threading\n"
               "t = threading.Thread(target=loop)\n"
               "t.start()\n")
        good = ("import threading\n"
                "t = threading.Thread(target=loop, daemon=True)\n"
                "t.start()\n")
        also_good = ("import threading\n"
                     "t = threading.Thread(target=loop)\n"
                     "t.daemon = True\n"
                     "t.start()\n")
        assert {"HVL005"} == _codes(_lint(bad))
        assert not _lint(good)
        assert not _lint(also_good)

    def test_hvl007_declared_but_not_propagated(self):
        cfg_rel = "horovod_tpu/common/config.py"
        src = ("KNOBS = {\n"
               "    'HOROVOD_PROPAGATED_KNOB': 1,\n"
               "    'HOROVOD_ORPHANED_KNOB': 2,\n"
               "}\n")
        findings = lint_source(
            src, rel_path=cfg_rel, declared=_DECLARED,
            propagated=frozenset({"HOROVOD_PROPAGATED_KNOB"}))
        assert [(f.code, f.line) for f in findings] == [("HVL007", 3)]
        assert "HOROVOD_ORPHANED_KNOB" in findings[0].message

    def test_hvl007_exemptions_and_scope(self):
        cfg_rel = "horovod_tpu/common/config.py"
        # bootstrap vars and harness-namespace knobs are launcher-exempt
        exempt = ("A = 'HOROVOD_KV_ADDR'\n"
                  "B = 'HVD_BENCH_SOMETHING'\n"
                  "C = 'HVD_LOCK_WITNESS'\n")
        assert not lint_source(exempt, rel_path=cfg_rel,
                               declared=_DECLARED, propagated=frozenset())
        # only the Config module is in scope for HVL007
        orphan = "K = 'HOROVOD_ORPHANED_KNOB'\n"
        assert not _lint(orphan)
        # inline suppression works like every other rule
        suppressed = ("K = 'HOROVOD_ORPHANED_KNOB'  "
                      "# hvdlint: disable=HVL007 -- driver-side only\n")
        assert not lint_source(suppressed, rel_path=cfg_rel,
                               declared=_DECLARED, propagated=frozenset())

    def test_hvl007_live_config_is_fully_propagated(self):
        """Every knob Config declares is exported by build_worker_env /
        the CLI arg map (or explicitly exempt) — the real files, not a
        corpus."""
        from horovod_tpu.analysis.lint import propagated_knobs
        prop = propagated_knobs()
        assert "HOROVOD_FUSION_THRESHOLD" in prop
        assert "HOROVOD_KV_RETRIES" in prop          # ISSUE 17 satellite
        cfg = os.path.join(_REPO, "horovod_tpu", "common", "config.py")
        with open(cfg) as f:
            findings = lint_source(f.read(),
                                   rel_path="horovod_tpu/common/config.py",
                                   declared=_DECLARED)
        assert not [f for f in findings if f.code == "HVL007"], \
            "\n".join(f.render() for f in findings)

    def test_suppression_requires_reason(self):
        suppressed = (
            "import os\n"
            "v = os.environ.get('HOROVOD_BOGUS')"
            "  # hvdlint: disable=HVL002 -- probe for a foreign build\n")
        no_reason = (
            "import os\n"
            "v = os.environ.get('HOROVOD_BOGUS')"
            "  # hvdlint: disable=HVL002\n")
        assert not _lint(suppressed)
        codes = _codes(_lint(no_reason))
        assert "HVL000" in codes and "HVL002" in codes

    def test_suppression_on_enclosing_line(self):
        src = ("import threading\n"
               "def arm():  # hvdlint: disable=HVL005 -- joined in stop()\n"
               "    t = threading.Thread(target=loop)\n"
               "    t.start()\n")
        assert not _lint(src)

    def test_skip_file_pragma(self):
        src = ("# hvdlint: skip-file -- generated code\n"
               "import os\n"
               "v = os.environ.get('HOROVOD_BOGUS')\n")
        assert not _lint(src)
        bare = ("# hvdlint: skip-file\n"
                "x = 1\n")
        assert {"HVL000"} == _codes(_lint(bare))

    def test_declared_knobs_parse_config(self):
        assert "HOROVOD_FUSION_THRESHOLD" in _DECLARED
        assert "HOROVOD_LOG_LEVEL" in _DECLARED       # ISSUE 9 satellite
        assert "HVD_FLASH_BLOCK" in _DECLARED
        assert "HOROVOD_NOT_A_KNOB" not in _DECLARED


# ---------------------------------------------------------------------------
# Tier-1 self-lint gate
# ---------------------------------------------------------------------------


class TestSelfLint:
    def test_repo_tree_is_clean_and_fast(self):
        """The repo's own scope (the scripts/lint.py default) lints clean
        — undeclared knobs, lock-held calls etc. fail tier-1 fast — and
        the full pass stays inside the 30 s budget."""
        scope = [os.path.join(_REPO, p)
                 for p in ("horovod_tpu", "examples", "scripts")
                 if os.path.exists(os.path.join(_REPO, p))]
        t0 = time.monotonic()
        findings, n_files = lint_paths(scope, base=_REPO)
        dt = time.monotonic() - t0
        assert n_files > 100
        assert not findings, "\n".join(f.render() for f in findings)
        assert dt < 30.0, f"lint took {dt:.1f}s (budget 30s)"

    def test_cli_entrypoint(self):
        """`python -m horovod_tpu.analysis.lint <clean file>` exits 0 and
        a bad file exits 1 (wired into CI shells)."""
        import subprocess
        import sys
        import tempfile

        with tempfile.TemporaryDirectory() as d:
            bad = os.path.join(d, "bad.py")
            with open(bad, "w") as f:
                f.write("import os\n"
                        "v = os.environ.get('HOROVOD_BOGUS_KNOB')\n")
            good = os.path.join(d, "good.py")
            with open(good, "w") as f:
                f.write("x = 1\n")
            env = dict(os.environ, PYTHONPATH=_REPO)
            r0 = subprocess.run(
                [sys.executable, "-m", "horovod_tpu.analysis.lint", good],
                capture_output=True, env=env, cwd=_REPO)
            r1 = subprocess.run(
                [sys.executable, "-m", "horovod_tpu.analysis.lint", bad],
                capture_output=True, env=env, cwd=_REPO)
        assert r0.returncode == 0, r0.stderr
        assert r1.returncode == 1
        assert b"HVL002" in r1.stdout


# ---------------------------------------------------------------------------
# hvdrace corpus: lock-graph rule classes, positive + negative
# ---------------------------------------------------------------------------


def _race(sources, rules=None):
    from horovod_tpu.analysis import race
    if isinstance(sources, str):
        sources = {"horovod_tpu/ops/x.py": sources}
    rep = race.analyze_sources(sources, rules=rules)
    return rep


def _race_codes(sources, rules=None):
    return {f.code for f in _race(sources, rules).findings}


class TestRaceRules:
    def test_hvr201_lock_order_inversion(self):
        bad = ("import threading\n"
               "_a = threading.Lock()\n"
               "_b = threading.Lock()\n"
               "def f():\n"
               "    with _a:\n"
               "        with _b:\n"
               "            pass\n"
               "def g():\n"
               "    with _b:\n"
               "        with _a:\n"
               "            pass\n")
        rep = _race(bad)
        assert {f.code for f in rep.findings} == {"HVR201"}
        # both witness paths are in the message
        msg = rep.findings[0].message
        assert "f" in msg and "g" in msg
        good = bad.replace("    with _b:\n        with _a:",
                           "    with _a:\n        with _b:")
        assert not _race(good).findings

    def test_hvr201_inversion_through_call_graph(self):
        """f holds _a then calls h (which takes _b); g nests the other
        way — only visible with hold propagation across calls."""
        bad = ("import threading\n"
               "_a = threading.Lock()\n"
               "_b = threading.Lock()\n"
               "def h():\n"
               "    with _b:\n"
               "        pass\n"
               "def f():\n"
               "    with _a:\n"
               "        h()\n"
               "def g():\n"
               "    with _b:\n"
               "        with _a:\n"
               "            pass\n")
        assert _race_codes(bad) == {"HVR201"}

    def test_hvr202_blocking_call_under_lock(self):
        bad = ("import threading\n"
               "import time\n"
               "_l = threading.Lock()\n"
               "def f():\n"
               "    with _l:\n"
               "        time.sleep(0.1)\n")
        rep = _race(bad)
        assert [(f.code, f.line) for f in rep.findings] == [("HVR202", 6)]
        good = ("import threading\n"
                "import time\n"
                "_l = threading.Lock()\n"
                "def f():\n"
                "    with _l:\n"
                "        n = 1\n"
                "    time.sleep(0.1)\n")
        assert not _race(good).findings

    def test_hvr202_propagated_hold_anchors_at_root_call(self):
        """The lock is held in f; the sleep lives in g.  The finding
        anchors at f's call into the held region — the line a human
        must fix — not inside g."""
        bad = ("import threading\n"
               "import time\n"
               "_l = threading.Lock()\n"
               "def g():\n"
               "    time.sleep(0.5)\n"
               "def f():\n"
               "    with _l:\n"
               "        g()\n")
        rep = _race(bad)
        assert [(f.code, f.line) for f in rep.findings] == [("HVR202", 8)]

    def test_hvr203_guarded_field_escape(self):
        bad = ("import threading\n"
               "class C:\n"
               "    def __init__(self):\n"
               "        self._lock = threading.Lock()\n"
               "        self._n = 0\n"
               "    def inc(self):\n"
               "        with self._lock:\n"
               "            self._n += 1\n"
               "    def peek(self):\n"
               "        return self._n\n")
        rep = _race(bad)
        assert {f.code for f in rep.findings} == {"HVR203"}
        assert "_n" in rep.findings[0].message
        good = bad.replace("        return self._n",
                           "        with self._lock:\n"
                           "            return self._n")
        assert not _race(good).findings

    def test_hvr203_init_writes_exempt(self):
        src = ("import threading\n"
               "class C:\n"
               "    def __init__(self):\n"
               "        self._lock = threading.Lock()\n"
               "        self._n = 0\n"
               "    def inc(self):\n"
               "        with self._lock:\n"
               "            self._n += 1\n")
        assert not _race(src).findings

    def test_hvr203_module_global(self):
        bad = ("import threading\n"
               "_lock = threading.Lock()\n"
               "_table = {}\n"
               "def put(k, v):\n"
               "    with _lock:\n"
               "        _table[k] = v\n"
               "def drop(k):\n"
               "    _table.pop(k, None)\n")
        assert _race_codes(bad) == {"HVR203"}

    def test_hvr204_signal_handler_unbounded_acquire(self):
        bad = ("import signal\n"
               "import threading\n"
               "_l = threading.Lock()\n"
               "def dump():\n"
               "    with _l:\n"
               "        pass\n"
               "def handler(signum, frame):\n"
               "    dump()\n"
               "signal.signal(signal.SIGTERM, handler)\n")
        rep = _race(bad)
        assert {f.code for f in rep.findings} == {"HVR204"}
        assert "handler" in rep.findings[0].message
        good = bad.replace("    with _l:\n        pass",
                           "    if _l.acquire(timeout=0.5):\n"
                           "        _l.release()")
        assert not _race(good).findings

    def test_hvr205_thread_leak_vs_shutdown_closure(self):
        bad = ("import threading\n"
               "def arm_watch():\n"
               "    t = threading.Thread(target=_loop, daemon=True)\n"
               "    t.start()\n"
               "def _loop():\n"
               "    pass\n")
        assert _race_codes(bad) == {"HVR205"}
        good = ("import atexit\n"
                "import threading\n"
                "_stop = threading.Event()\n"
                "def arm_watch():\n"
                "    t = threading.Thread(target=_loop, daemon=True)\n"
                "    t.start()\n"
                "def stop_watch():\n"
                "    _stop.set()\n"
                "def _loop():\n"
                "    pass\n"
                "def _cleanup():\n"
                "    stop_watch()\n"
                "atexit.register(_cleanup)\n")
        assert not _race(good).findings

    def test_suppression_semantics(self):
        base = ("import threading\n"
                "import time\n"
                "_l = threading.Lock()\n"
                "def f():\n"
                "    with _l:\n"
                "        time.sleep(0.1){}\n")
        reasoned = base.format(
            "  # hvdrace: disable=HVR202 -- bounded poll, test-only")
        assert not _race(reasoned).findings
        bare = base.format("  # hvdrace: disable=HVR202")
        codes = _race_codes(bare)
        assert "HVR200" in codes and "HVR202" in codes
        on_def = base.format("").replace(
            "def f():",
            "def f():  # hvdrace: disable=HVR202 -- whole-function waiver")
        assert not _race(on_def).findings

    def test_skip_file_and_syntax_error(self):
        skipped = ("# hvdrace: skip-file -- vendored\n"
                   "import threading\n"
                   "import time\n"
                   "_l = threading.Lock()\n"
                   "def f():\n"
                   "    with _l:\n"
                   "        time.sleep(1)\n")
        assert not _race(skipped).findings
        assert _race_codes("def f(:\n") == {"HVR999"}


# ---------------------------------------------------------------------------
# witness cross-check: synthetic log vs the static graph
# ---------------------------------------------------------------------------


class TestWitnessCrossCheck:
    _SRC = {
        "horovod_tpu/alpha.py": (
            "import threading\n"
            "from horovod_tpu import beta\n"
            "_outer = threading.Lock()\n"
            "def work():\n"
            "    with _outer:\n"
            "        beta.record()\n"),
        "horovod_tpu/beta.py": (
            "import threading\n"
            "_inner = threading.Lock()\n"
            "def record():\n"
            "    with _inner:\n"
            "        pass\n"),
    }

    def test_predicted_edge_is_green(self):
        from horovod_tpu.analysis import race
        rep = _race(dict(self._SRC))
        assert not rep.findings
        assert ("alpha:_outer", "beta:_inner") in rep.edges
        ok = race.cross_check(rep, {("alpha:_outer", "beta:_inner"): 3})
        assert ok == []

    def test_unpredicted_edge_is_hvr210(self):
        from horovod_tpu.analysis import race
        rep = _race(dict(self._SRC))
        bad = race.cross_check(rep, {("beta:_inner", "alpha:_outer"): 1})
        assert [f.code for f in bad] == ["HVR210"]
        assert "beta:_inner -> alpha:_outer" in bad[0].message

    def test_unknown_lock_is_hvr211(self):
        from horovod_tpu.analysis import race
        rep = _race(dict(self._SRC))
        bad = race.cross_check(rep, {("gamma:_mystery", "beta:_inner"): 1})
        assert [f.code for f in bad] == ["HVR211"]

    def test_site_ident_resolves_via_lock_table(self):
        """Factory-created locks report allocation sites
        ('<rel>.py:<line>'); cross_check maps them back through the
        static lock table."""
        from horovod_tpu.analysis import race
        rep = _race(dict(self._SRC))
        assert rep.lock_table[("horovod_tpu/alpha.py", 3)] == "alpha:_outer"
        site_edges = {("horovod_tpu/alpha.py:3", "horovod_tpu/beta.py:2"): 2}
        assert race.cross_check(rep, site_edges) == []

    def test_dump_load_roundtrip(self, tmp_path):
        from horovod_tpu.analysis import race
        race.uninstall_witness()
        race.reset_witness_edges()
        race._witness_edges[("alpha:_outer", "beta:_inner")] = 5
        p = str(tmp_path / "witness.jsonl")
        race.dump_witness(p)
        loaded = race.load_witness(p)
        assert loaded == {("alpha:_outer", "beta:_inner"): 5}
        race.reset_witness_edges()


# ---------------------------------------------------------------------------
# Tier-1 self-race gate + live witness cross-check
# ---------------------------------------------------------------------------


class TestSelfRace:
    def test_repo_tree_is_clean_and_fast(self):
        """The package's lock graph analyzes clean — order inversions,
        blocking-under-lock, guarded-field escapes etc. fail tier-1
        fast — and the whole-package pass stays inside the 30 s
        budget."""
        from horovod_tpu.analysis import race
        t0 = time.monotonic()
        rep = race.analyze_paths(
            [os.path.join(_REPO, "horovod_tpu")], base=_REPO)
        dt = time.monotonic() - t0
        assert rep.n_files > 100
        assert len(rep.edges) > 20          # the graph is real, not empty
        assert not rep.findings, "\n".join(f.render() for f in rep.findings)
        assert dt < 30.0, f"hvdrace took {dt:.1f}s (budget 30s)"

    def test_cli_entrypoint(self):
        """`python -m horovod_tpu.analysis.race <bad file>` exits 1 with
        the rule id on stdout; a clean file exits 0."""
        import subprocess
        import tempfile

        bad_src = ("import threading\n"
                   "_a = threading.Lock()\n"
                   "_b = threading.Lock()\n"
                   "def f():\n"
                   "    with _a:\n"
                   "        with _b:\n"
                   "            pass\n"
                   "def g():\n"
                   "    with _b:\n"
                   "        with _a:\n"
                   "            pass\n")
        with tempfile.TemporaryDirectory() as d:
            bad = os.path.join(d, "bad.py")
            with open(bad, "w") as f:
                f.write(bad_src)
            good = os.path.join(d, "good.py")
            with open(good, "w") as f:
                f.write("x = 1\n")
            env = dict(os.environ, PYTHONPATH=_REPO)
            r0 = subprocess.run(
                [sys.executable, "-m", "horovod_tpu.analysis.race", good],
                capture_output=True, env=env, cwd=_REPO)
            r1 = subprocess.run(
                [sys.executable, "-m", "horovod_tpu.analysis.race", bad],
                capture_output=True, env=env, cwd=_REPO)
        assert r0.returncode == 0, r0.stderr
        assert r1.returncode == 1
        assert b"HVR201" in r1.stdout

    def test_lint_script_race_mode_json_stream(self):
        """`scripts/lint.py --race --format json` runs hvdlint AND
        hvdrace and stdout stays a parseable stream of JSON
        documents."""
        import subprocess

        r = subprocess.run(
            [sys.executable, os.path.join(_REPO, "scripts", "lint.py"),
             "--race", "--format", "json"],
            capture_output=True, text=True,
            env=dict(os.environ, PYTHONPATH=_REPO), cwd=_REPO)
        assert r.returncode == 0, r.stdout + r.stderr
        docs = []
        dec = json.JSONDecoder()
        buf = r.stdout.strip()
        while buf:
            doc, idx = dec.raw_decode(buf)
            docs.append(doc)
            buf = buf[idx:].lstrip()
        assert len(docs) == 2
        race_doc = docs[-1]
        assert race_doc["files"] > 100
        assert len(race_doc["edges"]) > 20
        assert race_doc["findings"] == []


class TestLockWitnessLive:
    def test_cross_check_live_serving_autopilot_telemetry(self, hvd):
        """Runtime acquisition-order witness over a real multi-threaded
        scenario — re-init, a serving engine fed from submitter threads
        through a commit/restore cycle, a telemetry agent, an autopilot
        controller, all in ONE process — then every observed edge must
        be predicted by the static may-hold-before graph."""
        import threading

        from horovod_tpu.analysis import race

        race.install_witness()
        kv = agent = None
        try:
            race.reset_witness_edges()
            # Full re-init under the witness: basics._lock -> recorder /
            # telemetry / trace edges are recorded live.
            hvd.shutdown()
            hvd.init()

            from horovod_tpu.models import GPT, GPTConfig
            from horovod_tpu.serving import ServingEngine

            cfg = GPTConfig.tiny(tp_axis=None, ep_axis=None,
                                 max_position_embeddings=32)
            model = GPT(cfg)
            params = model.init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 4), jnp.int32))["params"]
            eng = ServingEngine(model, params, num_slots=2,
                                mark_steps=False)
            assert type(eng._submit_lock).__name__ == "_WitnessProxy"

            reqs = []
            submit_lock = threading.Lock()   # test-owned, not witnessed

            def submitter(seed):
                rng = np.random.default_rng(seed)
                for _ in range(3):
                    p = [int(t) for t in
                         rng.integers(0, cfg.vocab_size, 3)]
                    r = eng.submit(p, max_new=3)
                    with submit_lock:
                        reqs.append(r)

            threads = [threading.Thread(target=submitter, args=(s,))
                       for s in (1, 2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for _ in range(3):
                eng.step()
            snap = eng.request_snapshot()        # commit (trace emits)
            eng.load_request_snapshot(snap)      # restore
            eng.run_until_idle()
            assert all(r.done() for r in reqs)

            from horovod_tpu.runner.http_kv import KVStoreServer
            from horovod_tpu.telemetry.aggregator import TelemetryAgent

            kv = KVStoreServer(secret="")
            clock = [1000.0]
            agent = TelemetryAgent(kv, rank=0, world=1, num_slices=1,
                                   interval=1.0, gen="0",
                                   include_metrics=False,
                                   time_fn=lambda: clock[0])
            for _ in range(3):
                clock[0] += 1.0
                agent.tick()

            from horovod_tpu.autopilot.controller import AutopilotController
            from horovod_tpu.common.config import Config

            ctrl = AutopilotController(Config(
                autopilot=True, autotune_warmup_samples=0,
                autotune_bayes_opt_max_samples=3))
            ctrl.tick()
            ctrl.tick()
        finally:
            if agent is not None:
                agent.stop()
            if kv is not None:
                kv.stop()
            race.uninstall_witness()

        edges = race.witness_edges()
        assert edges, "witness recorded no acquisition edges"
        rep = race.analyze_paths(
            [os.path.join(_REPO, "horovod_tpu")], base=_REPO)
        assert not rep.findings
        bad = race.cross_check(rep, edges)
        assert not bad, "\n".join(f.render() for f in bad)
