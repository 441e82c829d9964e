"""Zero-downtime stream state: snapshot schema, bit-exact migration, drills.

The serving durability contract, end to end:

  * ``StreamSessionManager.state_dict`` is a deterministic, alias-free,
    schema-versioned tree (pinned here — changing the layout must bump
    ``SESSION_SCHEMA_VERSION``);
  * ``CompiledSNN.snapshot`` -> ``spidr.restore`` migrates live streams
    onto a freshly compiled replica **bit-exactly**: same spikes, readout
    and cumulative cycle/energy attribution as the uninterrupted run, for
    fused-Pallas and jnp backends, 1 and 4 cores, any snapshot tick, any
    chunking, any slot open/close interleaving;
  * the streaming server rewinds poisoned/hung ticks
    (``runtime.fault_tolerance``) and restores across process death
    (``tools/upgrade_drill.py`` runs the full kill matrix in CI).
"""
import functools
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # dev extra absent: property tests skip, rest run
    from _hypothesis_stub import given, settings, st

import repro
from repro import spidr
from repro.configs import spidr_gesture, spidr_optflow
from repro.core.network import init_params
from repro.engine.streaming import SESSION_SCHEMA_VERSION
from repro.serving import StreamRequest, StreamWorker
from repro.runtime.fault_tolerance import RestartableFailure

HW, T = (16, 16), 6


def _spec(task: str):
    mod = spidr_gesture if task == "gesture" else spidr_optflow
    return mod.reduced(hw=HW, timesteps=T)


@functools.lru_cache(maxsize=None)
def _compiled(task="gesture", backend="jnp", n_cores=1, seed=0,
              chunk_T=2, capacity=3):
    spec = _spec(task)
    params = init_params(jax.random.PRNGKey(seed), spec)
    target = spidr.DeployTarget(weight_bits=4, backend=backend,
                                n_cores=n_cores, chunk_T=chunk_T,
                                stream_capacity=capacity)
    return spidr.compile(spec, params, target)


def _chunk(rng, t):
    return (rng.random((t,) + HW + (2,)) < 0.1).astype(np.float32)


def _update_key(up):
    return (up.timesteps, np.asarray(up.readout).tolist(), up.chunk_spikes,
            up.spikes, up.cycles, up.energy_uj,
            None if up.per_core_cycles is None
            else np.asarray(up.per_core_cycles).tolist(),
            up.load_imbalance)


# ---------------------------------------------------------------------------
# The serialized-session schema (satellite: deterministic serializable view).
# ---------------------------------------------------------------------------
class TestSessionStateDict:
    def test_schema_is_pinned(self):
        # Changing this layout is a compatibility break: bump
        # SESSION_SCHEMA_VERSION and teach load_state_dict the old form.
        assert SESSION_SCHEMA_VERSION == 1
        sess = _compiled().open_stream(2, 2)
        sess.open()
        d = sess.state_dict()
        assert sorted(d) == ["clocks", "engine_state", "schema", "table"]
        assert int(d["schema"]) == SESSION_SCHEMA_VERSION
        assert sorted(d["engine_state"]) == [
            "in_counts", "out_counts", "readout_acc", "vmem"]
        assert sorted(d["table"]) == [
            "active", "core_cycles", "cycles", "ended", "energy_uj",
            "imbalance", "route_cycles", "spikes", "ticks", "timesteps"]
        assert d["table"]["active"].dtype == np.bool_
        assert d["table"]["timesteps"].dtype == np.int64
        assert d["table"]["energy_uj"].dtype == np.float64
        # One clock set per slot per core, fixed even for idle slots.
        assert len(d["clocks"]) == 2
        assert all(len(c) == 1 for c in d["clocks"])
        assert sorted(d["clocks"][0][0]) == [
            "cm_busy", "cm_free", "nu_busy", "nu_free", "recv_ready",
            "total_T", "worst_compute"]

    def test_state_dict_never_aliases_live_state(self):
        compiled = _compiled()
        sess = compiled.open_stream(2, 2)
        s0 = sess.open()
        rng = np.random.default_rng(0)
        sess.step({s0: _chunk(rng, 2)})
        frozen = sess.state_dict()
        # Corrupt every array in the snapshot...
        def smash(x):
            if isinstance(x, np.ndarray) and x.ndim:
                x.fill(-1)
        jax.tree.map(smash, frozen, is_leaf=lambda x: x is None)
        # ...and the live session must not notice.
        clean = sess.state_dict()
        assert int(clean["table"]["timesteps"][s0]) == 2
        assert not np.array_equal(clean["table"]["timesteps"],
                                  frozen["table"]["timesteps"])

    def test_state_dict_is_immutable_evidence_of_its_tick(self):
        compiled = _compiled()
        sess = compiled.open_stream(2, 2)
        s0 = sess.open()
        rng = np.random.default_rng(1)
        sess.step({s0: _chunk(rng, 2)})
        at_tick_1 = sess.state_dict()
        t1 = int(at_tick_1["table"]["timesteps"][s0])
        sess.step({s0: _chunk(rng, 2)})
        assert int(at_tick_1["table"]["timesteps"][s0]) == t1

    def test_roundtrip_through_fresh_session_is_bit_exact(self):
        compiled = _compiled()
        sess = compiled.open_stream(3, 2)
        s0, s1 = sess.open(), sess.open()
        rng = np.random.default_rng(2)
        for _ in range(2):
            sess.step({s0: _chunk(rng, 2), s1: _chunk(rng, 2)})
        snap = sess.state_dict()
        later = [{s0: _chunk(rng, 2), s1: _chunk(rng, 2)}]
        ref = [sess.step(c) for c in later]
        twin = compiled.open_stream(3, 2)
        twin.load_state_dict(snap)
        assert twin.active == (True, True, False)
        got = [twin.step(c) for c in later]
        for r, g in zip(ref, got):
            for slot in r:
                assert _update_key(r[slot]) == _update_key(g[slot])

    def test_newer_schema_is_refused(self):
        sess = _compiled().open_stream(2, 2)
        snap = sess.state_dict()
        snap["schema"] = np.int64(SESSION_SCHEMA_VERSION + 1)
        with pytest.raises(ValueError, match="schema"):
            sess.load_state_dict(snap)

    def test_capacity_mismatch_is_refused(self):
        compiled = _compiled()
        snap = compiled.open_stream(2, 2).state_dict()
        with pytest.raises(ValueError, match="capacity"):
            compiled.open_stream(3, 2).load_state_dict(snap)

    def test_clock_layout_mismatch_is_refused(self):
        compiled = _compiled()
        snap = compiled.open_stream(2, 2).state_dict()
        snap["clocks"] = [c + c for c in snap["clocks"]]  # pretend 2 cores
        with pytest.raises(ValueError, match="clock layout"):
            compiled.open_stream(2, 2).load_state_dict(snap)

    def test_wrong_network_is_refused(self):
        snap = _compiled("gesture").open_stream(2, 2).state_dict()
        with pytest.raises(ValueError, match="Vmem shapes"):
            _compiled("optical-flow").open_stream(2, 2).load_state_dict(snap)

    def test_slot_update_spikes_is_cumulative(self):
        sess = _compiled().open_stream(2, 2)
        s0 = sess.open()
        rng = np.random.default_rng(3)
        total = 0
        for _ in range(3):
            up = sess.step({s0: _chunk(rng, 2)})[s0]
            total += up.chunk_spikes
            assert up.spikes == total


# ---------------------------------------------------------------------------
# Tentpole: snapshot -> restore migration is bit-exact (the proof matrix).
# ---------------------------------------------------------------------------
MATRIX = [
    ("gesture", "jnp", 1),
    ("gesture", "fused", 1),
    ("gesture", "jnp", 4),
    ("optical-flow", "jnp", 1),
    ("optical-flow", "fused", 4),
]


class TestSnapshotRestoreMigration:
    @pytest.mark.parametrize("task,backend,n_cores", MATRIX)
    def test_migrated_stream_is_bit_identical(self, tmp_path, task,
                                              backend, n_cores):
        compiled = _compiled(task, backend, n_cores)
        sess = compiled.open_stream(3, 2)
        s0, s1 = sess.open(), sess.open()
        rng = np.random.default_rng(7)
        for _ in range(2):
            sess.step({s0: _chunk(rng, 2), s1: _chunk(rng, 2)})
        compiled.snapshot(str(tmp_path), step=2, sessions=[sess],
                          extra={"tick": 2})
        # Continue the original: one full tick, then s1 ends on a short
        # final chunk (slot churn after the snapshot point).
        later = [{s0: _chunk(rng, 2), s1: _chunk(rng, 2)},
                 {s0: _chunk(rng, 2), s1: _chunk(rng, 1)}]
        ref = [sess.step(c) for c in later]

        restored = spidr.restore(str(tmp_path))
        assert restored is not compiled
        assert restored.target == compiled.target
        twin = restored.sessions[0]
        assert twin.active == (True, True, False)
        got = [twin.step(c) for c in later]
        for r, g in zip(ref, got):
            assert sorted(r) == sorted(g)
            for slot in r:
                assert _update_key(r[slot]) == _update_key(g[slot])
        # Slot churn stays in lockstep after migration: retire the ended
        # stream, admit a new one, and both sessions keep agreeing.
        sess.close(s1)
        twin.close(s1)
        n0, n1 = sess.open(), twin.open()
        assert n0 == n1
        tick = {s0: _chunk(rng, 2), n0: _chunk(rng, 2)}
        r, g = sess.step(tick), twin.step(tick)
        for slot in r:
            assert _update_key(r[slot]) == _update_key(g[slot])

    def test_snapshot_restore_of_exported_network(self, tmp_path):
        from repro.core.quant import QuantSpec
        from repro.snn.export import export_network

        spec = _spec("gesture")
        params = init_params(jax.random.PRNGKey(0), spec)
        exported = export_network(params, spec, QuantSpec(4))
        target = spidr.DeployTarget(weight_bits=4, chunk_T=2,
                                    stream_capacity=2)
        compiled = spidr.compile(exported, spec, target)
        sess = compiled.open_stream()
        s0 = sess.open()
        rng = np.random.default_rng(11)
        sess.step({s0: _chunk(rng, 2)})
        compiled.snapshot(str(tmp_path), sessions=[sess])
        restored = spidr.restore(str(tmp_path))
        assert restored.exported is not None  # provenance survives
        later = {s0: _chunk(rng, 2)}
        assert _update_key(sess.step(later)[s0]) \
            == _update_key(restored.sessions[0].step(later)[s0])

    def test_restore_onto_prepared_replica(self, tmp_path):
        compiled = _compiled()
        sess = compiled.open_stream(2, 2)
        s0 = sess.open()
        rng = np.random.default_rng(13)
        sess.step({s0: _chunk(rng, 2)})
        compiled.snapshot(str(tmp_path), sessions=[sess])
        # Same weights and target, but a genuinely distinct CompiledSNN.
        replica = _compiled.__wrapped__("gesture", "jnp", 1, 0, 2, 3)
        assert replica is not compiled
        before = len(replica.sessions)
        out = spidr.restore(str(tmp_path), compiled=replica)
        assert out is replica and len(replica.sessions) == before + 1
        later = {s0: _chunk(rng, 2)}
        assert _update_key(sess.step(later)[s0]) \
            == _update_key(replica.sessions[-1].step(later)[s0])

    def test_replica_with_different_target_is_refused(self, tmp_path):
        compiled = _compiled()
        compiled.snapshot(str(tmp_path), sessions=[])
        other = _compiled(backend="fused")
        with pytest.raises(ValueError, match="DeployTarget"):
            spidr.restore(str(tmp_path), compiled=other)

    def test_replica_with_different_weights_is_refused(self, tmp_path):
        compiled = _compiled()
        compiled.snapshot(str(tmp_path), sessions=[])
        other = _compiled(seed=1)
        with pytest.raises(ValueError, match="identical"):
            spidr.restore(str(tmp_path), compiled=other)

    def test_non_snapshot_checkpoint_is_refused(self, tmp_path):
        from repro.checkpoint.checkpoint import Checkpointer

        Checkpointer(str(tmp_path)).save(0, {"w": np.zeros(3)})
        with pytest.raises(ValueError, match="not a spidr session snapshot"):
            spidr.restore(str(tmp_path))
        with pytest.raises(ValueError):
            spidr.read_snapshot_meta(str(tmp_path))

    def test_missing_snapshot_is_file_not_found(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            spidr.restore(str(tmp_path / "nothing"))

    def test_snapshot_meta_round_trips_bookkeeping(self, tmp_path):
        compiled = _compiled()
        extra = {"cursors": {"0": 4}, "note": "pre-upgrade"}
        compiled.snapshot(str(tmp_path), step=9, sessions=[], extra=extra)
        info = spidr.read_snapshot_meta(str(tmp_path))
        assert info["step"] == 9
        assert info["extra"] == extra
        assert info["spec"]["input_hw"] == list(HW)
        assert info["target"]["n_cores"] == 1

    def test_migration_across_processes(self, tmp_path):
        # The real thing, minimally: snapshot here, resume in a fresh
        # interpreter (cold jax, cold caches), byte-compare the replies.
        compiled = _compiled()
        sess = compiled.open_stream(2, 2)
        s0 = sess.open()
        rng = np.random.default_rng(17)
        sess.step({s0: _chunk(rng, 2)})
        compiled.snapshot(str(tmp_path / "snap"), sessions=[sess])
        later = _chunk(rng, 2)
        np.save(tmp_path / "later.npy", later)
        ref = _update_key(sess.step({s0: later})[s0])

        child = (
            "import json, sys, numpy as np\n"
            "from repro import spidr\n"
            "c = spidr.restore(sys.argv[1])\n"
            "up = c.sessions[0].step({0: np.load(sys.argv[2])})[0]\n"
            "print(json.dumps([up.timesteps, np.asarray(up.readout).tolist(),"
            " up.chunk_spikes, up.spikes, up.cycles, up.energy_uj,"
            " None if up.per_core_cycles is None else"
            " np.asarray(up.per_core_cycles).tolist(), up.load_imbalance]))\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        src = os.path.dirname(os.path.dirname(os.path.abspath(
            repro.__file__)))
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        out = subprocess.run(
            [sys.executable, "-c", child, str(tmp_path / "snap"),
             str(tmp_path / "later.npy")],
            env=env, capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        assert tuple(json.loads(out.stdout.strip().splitlines()[-1])) \
            == tuple(json.loads(json.dumps(list(ref))))


# ---------------------------------------------------------------------------
# Invariance properties: any snapshot tick, any chunking, any interleaving.
# ---------------------------------------------------------------------------
def _serve(compiled, lens, seed, chunk_T, snapshot_tick=None, tmp=None):
    """Serve seeded streams of the given lengths; optionally snapshot at a
    tick and finish on a server restored from disk.  Returns {rid: result}."""
    def requests():
        rng = np.random.default_rng(seed)
        return {rid: StreamRequest(rid=rid, events=(
            rng.random((t,) + HW + (2,)) < 0.1).astype(np.float32))
            for rid, t in enumerate(lens)}

    server = StreamWorker(
        compiled, capacity=2, chunk_T=chunk_T,
        snapshot_dir=tmp if snapshot_tick is not None else None,
        snapshot_every=1 if snapshot_tick is not None else 0)
    for rid, req in sorted(requests().items()):
        server.submit(req)
    while server.step():
        if snapshot_tick is not None and server.ticks >= snapshot_tick:
            server = StreamWorker.restore(tmp, requests(),
                                                compiled=compiled)
            snapshot_tick = None  # abandoned mid-run, resumed from disk
    return {r.rid: (np.asarray(r.readout).tolist(), r.cycles, r.energy_uj)
            for r in server.done}


class TestInvariance:
    def test_every_snapshot_tick_restores_identically(self, tmp_path):
        lens = [6, 4, 5, 6]
        compiled = _compiled(chunk_T=2, capacity=2)
        ref = _serve(compiled, lens, seed=23, chunk_T=2)
        total_ticks = 7  # 2 slots x interleaved admissions
        for k in range(1, total_ticks):
            tmp = str(tmp_path / f"t{k}")
            got = _serve(compiled, lens, seed=23, chunk_T=2,
                         snapshot_tick=k, tmp=tmp)
            assert got == ref, f"diverged when killed after tick {k}"

    def test_chunking_invariance_survives_migration(self, tmp_path):
        lens = [6, 5, 4]
        results = {}
        for chunk_T in (1, 2, 3):
            compiled = _compiled(chunk_T=chunk_T, capacity=2)
            tmp = str(tmp_path / f"c{chunk_T}")
            results[chunk_T] = _serve(compiled, lens, seed=29,
                                      chunk_T=chunk_T, snapshot_tick=2,
                                      tmp=tmp)
        # Readout and cycle attribution are chunking-invariant integers, so
        # every chunking (each snapshotted/restored mid-run) must agree
        # exactly; energy is a float sum whose order follows the chunk
        # boundaries, so across *different* chunkings it only matches to
        # rounding (within one chunking it is bit-exact — tests above).
        for chunk_T in (2, 3):
            assert sorted(results[chunk_T]) == sorted(results[1])
            for rid, (readout, cycles, energy) in results[1].items():
                r2, c2, e2 = results[chunk_T][rid]
                assert (r2, c2) == (readout, cycles)
                assert e2 == pytest.approx(energy, rel=1e-12)

    def test_multicore_interleaving_restores_identically(self, tmp_path):
        lens = [6, 3, 5, 4]
        compiled = _compiled(n_cores=4, chunk_T=2, capacity=2)
        ref = _serve(compiled, lens, seed=31, chunk_T=2)
        got = _serve(compiled, lens, seed=31, chunk_T=2, snapshot_tick=3,
                     tmp=str(tmp_path / "mc"))
        assert got == ref

    @given(k=st.integers(min_value=1, max_value=6),
           seed=st.integers(min_value=0, max_value=2**16),
           chunk_T=st.integers(min_value=1, max_value=3))
    @settings(max_examples=8, deadline=None)
    def test_property_restore_matches_uninterrupted(self, tmp_path_factory,
                                                    k, seed, chunk_T):
        rng = np.random.default_rng(seed)
        lens = [int(rng.integers(2, T + 1)) for _ in range(3)]
        compiled = _compiled(chunk_T=chunk_T, capacity=2)
        ref = _serve(compiled, lens, seed=seed, chunk_T=chunk_T)
        tmp = str(tmp_path_factory.mktemp("prop"))
        got = _serve(compiled, lens, seed=seed, chunk_T=chunk_T,
                     snapshot_tick=k, tmp=tmp)
        assert got == ref


# ---------------------------------------------------------------------------
# The durable server: watchdog, rewind-and-replay, restart budget.
# ---------------------------------------------------------------------------
class TestDurableServer:
    def _requests(self, seed=37, lens=(6, 4, 5, 6)):
        rng = np.random.default_rng(seed)
        return {rid: StreamRequest(rid=rid, events=(
            rng.random((t,) + HW + (2,)) < 0.1).astype(np.float32))
            for rid, t in enumerate(lens)}

    def _run(self, server, reqs):
        for rid in sorted(reqs):
            server.submit(reqs[rid])
        while server.step():
            pass
        return {r.rid: (np.asarray(r.readout).tolist(), r.cycles,
                        r.energy_uj) for r in server.done}

    def test_poisoned_tick_rewinds_and_replays_bit_exactly(self):
        compiled = _compiled(capacity=2)
        ref = self._run(StreamWorker(compiled, 2, 2),
                        self._requests())
        srv = StreamWorker(compiled, 2, 2, fail_at_tick=3)
        got = self._run(srv, self._requests())
        assert srv.restarts == 1
        assert got == ref

    def test_hung_tick_trips_watchdog_then_recovers(self):
        compiled = _compiled(capacity=2)
        ref = self._run(StreamWorker(compiled, 2, 2),
                        self._requests())
        srv = StreamWorker(compiled, 2, 2, watchdog_s=0.05)
        real_step = srv.sessions.step
        hung = {"n": 0}

        def slow_once(chunks):
            out = real_step(chunks)
            if hung["n"] == 0:
                hung["n"] += 1
                import time
                time.sleep(0.2)  # blow the deadline exactly once
            return out

        srv.sessions.step = slow_once
        got = self._run(srv, self._requests())
        srv.sessions.step = real_step
        assert srv.restarts == 1
        assert got == ref

    def test_restart_budget_exhausts_into_failure(self):
        from repro.runtime.fault_tolerance import RestartableFailure as RF

        srv = StreamWorker(_compiled(capacity=2), 2, 2,
                                 max_restarts=2)

        def always_poisoned(tick):
            raise RF("wedged hardware")

        srv.mid_tick_hook = always_poisoned
        for rid, req in sorted(self._requests().items()):
            srv.submit(req)
        with pytest.raises(RestartableFailure, match="wedged"):
            srv.step()
        assert srv.restarts == 3  # 1 try + max_restarts replays


# ---------------------------------------------------------------------------
# The in-process rewind point: device state by reference, never copied.
# ---------------------------------------------------------------------------
def _state_leaves(st):
    """An ``EngineState``'s arrays, or a ``state_dict``'s engine part."""
    if isinstance(st, dict):
        return ([v for v in st["vmem"] if v is not None]
                + [st["readout_acc"], st["out_counts"], st["in_counts"]])
    return ([v for v in st.vmem if v is not None]
            + [st.readout_acc, st.out_counts, st.in_counts])


def _assert_same_snapshot(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)


class TestRewindPoint:
    @pytest.mark.parametrize("op", ["step", "close"])
    def test_mark_outlives_the_device_op_that_consumes_it(self, op):
        # The chunk step and reset_slot both take the marked state as
        # input: if either donated it, its arrays would be deleted here.
        sess = _compiled().open_stream(2, 2)
        s0 = sess.open()
        rng = np.random.default_rng(5)
        sess.step({s0: _chunk(rng, 2)})
        before = sess.state_dict()
        mark = sess.mark()
        if op == "step":
            sess.step({s0: _chunk(rng, 2)})
        else:
            sess.close(s0)
        leaves = _state_leaves(mark.state)
        assert not any(leaf.is_deleted() for leaf in leaves)
        for got, want in zip(leaves, _state_leaves(before["engine_state"])):
            np.testing.assert_array_equal(got, want)
        # Rewinding twice to the same mark lands on the same state.
        for _ in range(2):
            sess.rewind(mark)
            _assert_same_snapshot(sess.state_dict(), before)
            sess.step({s0: _chunk(rng, 2)})

    def test_worker_rewind_point_pins_the_pre_tick_state(self):
        srv = StreamWorker(_compiled(capacity=2), 2, 2)
        for rid, req in sorted(TestDurableServer()._requests().items()):
            srv.submit(req)
        ticks = 0
        while True:
            pre = _state_leaves(srv.sessions._manager.state)
            want = _state_leaves(srv.sessions.state_dict()["engine_state"])
            if not srv.step():
                break
            ticks += 1
            held = _state_leaves(srv._rewind_point["session"].state)
            assert all(h is p for h, p in zip(held, pre))
            assert not any(h.is_deleted() for h in held)
            for h, w in zip(held, want):
                np.testing.assert_array_equal(h, w)
        assert ticks == srv.ticks > 1

    def test_mark_holds_no_finished_request(self):
        srv = StreamWorker(_compiled(capacity=2), 2, 2)
        for rid, req in sorted(TestDurableServer()._requests().items()):
            srv.submit(req)
        finished_before_a_tick = 0
        while True:
            live = {id(r) for r in list(srv.slots.values()) + srv.waiting}
            finished = list(srv.done)
            alive = srv.step()
            cp = srv._rewind_point
            assert {id(r[0]) for r in cp["reqs"]} == live
            assert cp["done"] == len(finished)
            finished_before_a_tick += bool(finished)
            if not alive:
                break
        assert finished_before_a_tick and len(srv.done) == 4

    @pytest.mark.parametrize("n_cores", [1, 4])
    @pytest.mark.parametrize("fault", ["fail_at_tick", "after_close"])
    def test_poisoned_tick_with_retire_and_admit_replays_bit_exactly(
            self, fault, n_cores):
        # Capacity 2, chunk 2, clips of 4, 6, 4, 2 steps: tick 3 admits
        # clip 2 into the slot clip 0 freed, and finishes clip 1 and
        # resets its slot.  ``fail_at_tick`` poisons it after the chunk
        # step; ``after_close`` after clip 1 is already on ``done`` and its
        # slot reset.
        compiled = _compiled(n_cores=n_cores, capacity=2)
        rng = np.random.default_rng(41)
        clips = [(rng.random((t,) + HW + (2,)) < 0.1).astype(np.float32)
                 for t in (4, 6, 4, 2)]

        def serve(poison):
            fleet = spidr.serve(compiled, capacity=2, chunk_T=2, mode="sync")
            w = fleet.workers[0]
            if poison == "fail_at_tick":
                w.fail_at_tick = 3
            elif poison == "after_close":
                real_close, fired = w.sessions.close, []

                def close(slot=None):
                    real_close(slot)
                    if w.ticks + 1 == 3 and not fired:
                        fired.append(slot)
                        raise RestartableFailure("poisoned after a reset")

                w.sessions.close = close
            handles = [fleet.submit(ev, rid=rid)
                       for rid, ev in enumerate(clips)]
            fleet.drain()
            seen = fleet._done_seen[0]
            got = ([r.rid for r in w.done], seen, w.restarts,
                   [(h.status, np.asarray(h.readout).tolist(), h.cycles,
                     h.energy_uj) for h in handles])
            fleet.shutdown()
            return got

        ref_order, ref_seen, ref_restarts, ref = serve(None)
        order, seen, restarts, got = serve(fault)
        assert ref_restarts == 0 and restarts == 1
        assert order == ref_order == [0, 1, 2, 3]
        assert seen == ref_seen == 4
        assert got == ref
        assert all(status == "done" for status, *_ in got)
