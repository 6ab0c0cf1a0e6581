"""Elastic multi-host training service (ISSUE-15).

Covers the three tentpole pieces — the fake-host :class:`Supervisor`
(death/hang detection, world restart, reshape), the two-phase
multi-host checkpoint commit (``shard-<h>.part`` staging, filesystem
rendezvous, rank-0 ``COMMIT`` promotion, markerless-step-is-garbage),
and topology-elastic resume (bit-exact re-flattening of packed
FusedAdam + GradBuckets state across world sizes) — plus the
satellites: fsync durability of the base manager's rename commit,
multi-writer-safe stale-tmp sweeping (seeded-violation red tests),
restore fallback over a partially-committed multi-host step, the
attributable :class:`HangWatchdog` context, and the CLI wiring.

The full chaos trace (kills mid-part-write and mid-barrier, a
heartbeat wedge, a topology reshape — final loss records byte-exact)
is in the slow tier; its tier-1 coverage rides the ``elastic_resume`` /
``host_kill`` legs of ``tools/resilience_check.py --self``
(parametrized into the quick tier by ``tests/test_resilience.py``).
"""
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu import analysis
from apex_tpu.multi_tensor_apply.packing import ROW, PackSpec
from apex_tpu.resilience import (
    BarrierNotReady,
    ChaosError,
    ChaosHost,
    CheckpointManager,
    ElasticCheckpointManager,
    HangError,
    HangWatchdog,
    Heartbeat,
    Supervisor,
    WorldFailedError,
    capture,
    pack_spec_for_world,
    reflatten_flat,
    world_chunk_size,
)
from apex_tpu.resilience._elastic_host import (
    build_world,
    init_params,
    reference_records,
)
from apex_tpu.telemetry import RingBufferRecorder

REPO = Path(__file__).parent.parent


# ---------------------------------------------------------------------------
# world-aware layouts + re-flattening
# ---------------------------------------------------------------------------
class TestWorldLayout:
    def test_world_chunk_size_divisibility(self):
        assert world_chunk_size(256, 4) == 4 * ROW
        assert world_chunk_size(4 * ROW, 4) == 4 * ROW
        assert world_chunk_size(4 * ROW + 1, 4) == 8 * ROW
        with pytest.raises(ValueError):
            world_chunk_size(256, 0)

    @pytest.mark.parametrize("world", [1, 2, 4])
    def test_pack_spec_for_world_shard_clean(self, world):
        spec = pack_spec_for_world(init_params(), world, chunk_size=256)
        assert not analysis.check_pack_spec(spec, shard_count=world)
        bounds = spec.shard_bounds(world)
        assert bounds[0][0] == 0 and bounds[-1][1] == spec.total
        for lo, hi in bounds:
            assert (hi - lo) % ROW == 0

    def test_shard_bounds_red_indivisible(self):
        spec = PackSpec({"w": jnp.zeros((8,))}, chunk_size=ROW)
        assert spec.total == ROW
        with pytest.raises(ValueError, match="not divisible"):
            spec.shard_bounds(3)

    def test_grad_buckets_for_world_layouts_differ(self):
        _, b2, _, _ = build_world(2)
        _, b4, _, _ = build_world(4)
        # different worlds genuinely lay out differently (the reshard
        # path is not a no-op) yet both shard cleanly
        assert b2.spec.total != b4.spec.total
        assert b2.spec.offsets != b4.spec.offsets
        assert not analysis.check_pack_spec(b2.spec, shard_count=2)
        assert not analysis.check_pack_spec(b4.spec, shard_count=4)


class TestReflatten:
    def _filled(self, spec, seed=0):
        buf = np.zeros((spec.total,), np.float32)
        rng = np.random.default_rng(seed)
        mask = spec.valid_mask()
        buf[mask] = rng.standard_normal(int(mask.sum())).astype(np.float32)
        return buf

    def test_roundtrip_bitwise(self):
        _, b2, _, _ = build_world(2)
        _, b4, _, _ = build_world(4)
        buf = self._filled(b4.spec)
        out = reflatten_flat(b4.spec, b2.spec, buf)
        back = reflatten_flat(b2.spec, b4.spec, out)
        np.testing.assert_array_equal(back, buf)
        # per-leaf values unchanged bit-for-bit
        a = b4.spec.unpack(buf, cast=False)
        b = b2.spec.unpack(out, cast=False)
        for la, lb in zip(jax.tree_util.tree_leaves(a),
                          jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))

    def test_mismatched_templates_raise(self):
        _, b2, _, _ = build_world(2)
        other = PackSpec({"x": jnp.zeros((64, 64))}, chunk_size=1024)
        with pytest.raises(ValueError, match="different leaf"):
            reflatten_flat(other, b2.spec, np.zeros((other.total,),
                                                    np.float32))

    def test_wrong_length_buffer_raises(self):
        _, b2, _, _ = build_world(2)
        with pytest.raises(ValueError, match="shape"):
            reflatten_flat(b2.spec, b2.spec,
                           np.zeros((b2.spec.total + 1,), np.float32))

    def test_check_reshard_red_and_green(self):
        _, b2, _, _ = build_world(2)
        _, b4, _, _ = build_world(4)
        assert not analysis.check_reshard(b4.spec, b2.spec,
                                          old_count=4, new_count=2)
        other = PackSpec({"x": jnp.zeros((64, 64))}, chunk_size=1024)
        findings = analysis.check_reshard(other, b2.spec)
        assert any(f.code == "reshard_leaf_mismatch" for f in findings)


# ---------------------------------------------------------------------------
# two-phase multi-host commit
# ---------------------------------------------------------------------------
def _fresh_state(world, step=0, position=0):
    p, b, o, s = build_world(world)
    return capture(step, p, o.init(p), scaler=s.init_state(),
                   rng=jax.random.PRNGKey(42),
                   data={"position": position})


def _save_world(root, state, world, rec=None, barrier_timeout_s=30.0):
    """All-hosts save through W in-process manager instances (hosts > 0
    async — they wait for host 0's COMMIT in the background)."""
    mgrs = [ElasticCheckpointManager(root, host=h, world=world, sink=rec,
                                     barrier_timeout_s=barrier_timeout_s)
            for h in range(world)]
    for m in mgrs[1:]:
        m.save(state, blocking=False)
    mgrs[0].save(state, blocking=True)
    for m in mgrs[1:]:
        m.wait_until_finished()
    return mgrs


class TestTwoPhaseCommit:
    def test_commit_layout_and_marker(self, tmp_path):
        root = str(tmp_path / "ckpt")
        state = _fresh_state(2, step=3, position=3)
        mgrs = _save_world(root, state, 2)
        d = tmp_path / "ckpt" / "step_00000003"
        assert sorted(os.listdir(d)) == ["COMMIT", "shard-0.part",
                                         "shard-1.part"]
        commit = json.loads((d / "COMMIT").read_text())
        assert commit["world"] == 2 and commit["step"] == 3
        assert mgrs[0].all_steps() == [3]
        meta1 = json.loads(
            (d / "shard-1.part" / "meta.json").read_text())
        assert meta1["host"] == 1 and meta1["pid"] == os.getpid()
        assert "data" not in meta1  # replicated host state rides shard 0

    def test_markerless_step_is_garbage_fallback(self, tmp_path):
        """Satellite: restore over a PARTIALLY committed multi-host
        step (some shards present, no COMMIT) must skip back to the
        prior good step, emit ``checkpoint_fallback``, and raise
        nothing."""
        root = str(tmp_path / "ckpt")
        rec = RingBufferRecorder()
        good = _fresh_state(2, step=4, position=4)
        _save_world(root, good, 2, rec=rec)
        # a torn newer save: one shard landed, COMMIT never written
        torn = tmp_path / "ckpt" / "step_00000006" / "shard-0.part"
        torn.mkdir(parents=True)
        (torn / "meta.json").write_text(json.dumps(
            {"step": 6, "host": 0, "world": 2, "pid": os.getpid()}))
        m = ElasticCheckpointManager(root, host=0, world=2, sink=rec)
        restored = m.restore(_fresh_state(2))
        assert restored is not None and restored.step == 4
        falls = [r for r in rec.records
                 if r["event"] == "checkpoint_fallback"]
        assert [r["step"] for r in falls] == [6]
        assert "COMMIT" in falls[0]["error"] or "uncommitted" in \
            falls[0]["error"]

    def test_no_commit_without_all_shards(self, tmp_path):
        """Rank 0's barrier times out when a peer never lands its
        shard; the step stays markerless and the failure surfaces as a
        checkpoint_failed event + BarrierNotReady."""
        root = str(tmp_path / "ckpt")
        rec = RingBufferRecorder()
        m0 = ElasticCheckpointManager(root, host=0, world=2, sink=rec,
                                      barrier_timeout_s=0.5)
        with pytest.raises(BarrierNotReady):
            m0.save(_fresh_state(2, step=3), blocking=True)
        d = tmp_path / "ckpt" / "step_00000003"
        assert not (d / "COMMIT").exists()
        assert m0.all_steps() == []
        assert any(r["event"] == "checkpoint_failed"
                   for r in rec.records)
        # and restore never touches the markerless garbage
        assert m0.restore(_fresh_state(2)) is None

    def test_emergency_flush_commits_alone_and_restores(self, tmp_path):
        """A preemption flush cannot barrier (peers got the same
        SIGTERM at other steps): any host commits a complete
        world-of-1 checkpoint alone, and restore reshards it onto the
        real world like any topology change."""
        root = str(tmp_path / "ckpt")
        rec = RingBufferRecorder()
        _, state = reference_records(2, 3)  # non-trivial moments
        m1 = ElasticCheckpointManager(root, host=1, world=2, sink=rec,
                                      barrier_timeout_s=5.0)
        m1.save(state, emergency=True)  # NO peers ever show up
        assert m1.all_steps() == [3]
        commit = json.loads(
            (tmp_path / "ckpt" / "step_00000003" / "COMMIT").read_text())
        assert commit["world"] == 1 and commit["emergency"] is True
        m0 = ElasticCheckpointManager(root, host=0, world=2, sink=rec)
        restored = m0.restore(_fresh_state(2))
        assert restored.step == 3 and restored.data == {"position": 3}
        for a, b in zip(jax.tree_util.tree_leaves(restored.opt_state),
                        jax.tree_util.tree_leaves(state.opt_state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # GC treats it as an emergency checkpoint (retention-exempt)
        assert m0._is_emergency(str(tmp_path / "ckpt" /
                                    "step_00000003"))

    def test_barrier_rejects_stale_shard_from_other_world(self, tmp_path):
        """A dead incarnation's shard at a DIFFERENT world size must
        not satisfy the rendezvous — committing it would mix row
        layouts across topologies."""
        root = str(tmp_path / "ckpt")
        stale = tmp_path / "ckpt" / "step_00000003" / "shard-1.part"
        stale.mkdir(parents=True)
        (stale / "meta.json").write_text(json.dumps(
            {"step": 3, "host": 1, "world": 4, "pid": 1}))
        m0 = ElasticCheckpointManager(root, host=0, world=2,
                                      barrier_timeout_s=0.5)
        with pytest.raises(BarrierNotReady):
            m0.save(_fresh_state(2, step=3), blocking=True)
        assert not (tmp_path / "ckpt" / "step_00000003" /
                    "COMMIT").exists()


class TestElasticReshard:
    def test_restore_onto_other_worlds_bitwise(self, tmp_path):
        root = str(tmp_path / "ckpt")
        rec = RingBufferRecorder()
        # a real trained state (non-zero moments) at W=2
        _, head = reference_records(2, 3)
        _save_world(root, head, 2, rec=rec)
        s2 = head.opt_state.spec
        for new_world in (1, 4):
            m = ElasticCheckpointManager(root, host=0, world=new_world,
                                         sink=rec)
            restored = m.restore(_fresh_state(new_world))
            assert restored.step == 3
            assert restored.data == {"position": 3}
            sN = restored.opt_state.spec
            assert not analysis.check_pack_spec(sN,
                                               shard_count=new_world)
            for name in ("exp_avg", "exp_avg_sq", "master_params"):
                a = s2.unpack(np.asarray(getattr(head.opt_state, name)),
                              cast=False)
                b = sN.unpack(
                    np.asarray(getattr(restored.opt_state, name)),
                    cast=False)
                for la, lb in zip(jax.tree_util.tree_leaves(a),
                                  jax.tree_util.tree_leaves(b)):
                    np.testing.assert_array_equal(np.asarray(la),
                                                  np.asarray(lb))
            # scalars and replicated leaves ride along bit-exactly
            assert np.asarray(restored.opt_state.step) == \
                np.asarray(head.opt_state.step)
            for la, lb in zip(
                    jax.tree_util.tree_leaves(restored.params),
                    jax.tree_util.tree_leaves(head.params)):
                np.testing.assert_array_equal(np.asarray(la),
                                              np.asarray(lb))
        assert any(r["event"] == "checkpoint_reshard"
                   for r in rec.records)

    def test_resumed_records_bit_identical_to_uninterrupted(self, tmp_path):
        """The acceptance oracle in-process: W=4 head + W'=2 tail ==
        uninterrupted W'=2 run, byte-for-byte (f32 hex records)."""
        root = str(tmp_path / "ckpt")
        head_records, head = reference_records(4, 3)
        _save_world(root, head, 4)
        m = ElasticCheckpointManager(root, host=0, world=2)
        restored = m.restore(_fresh_state(2))
        tail_records, _ = reference_records(2, 6, start_state=restored)
        ref_records, _ = reference_records(2, 6)
        assert {**head_records, **tail_records} == ref_records


# ---------------------------------------------------------------------------
# satellite: fsync durability of the rename commit
# ---------------------------------------------------------------------------
class TestFsyncDurability:
    def test_commit_fsyncs_staged_tree_and_parent(self, tmp_path,
                                                  monkeypatch):
        from apex_tpu.resilience import manager as mgr_mod

        trees, dirs = [], []
        real_tree, real_dir = mgr_mod.fsync_tree, mgr_mod.fsync_dir
        monkeypatch.setattr(mgr_mod, "fsync_tree",
                            lambda p: (trees.append(p),
                                       real_tree(p))[1])
        monkeypatch.setattr(mgr_mod, "fsync_dir",
                            lambda p: (dirs.append(p), real_dir(p))[1])
        fsyncs = []
        real_fsync = os.fsync
        monkeypatch.setattr(os, "fsync",
                            lambda fd: (fsyncs.append(fd),
                                        real_fsync(fd))[1])
        m = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
        m.save(capture(2, {"w": jnp.arange(4.0)}, None), blocking=True)
        # the WHOLE staged tree (arrays + meta) flushed before the
        # rename, the parent directory after it
        assert any(".tmp-" in p for p in trees)
        assert m.root in dirs
        assert fsyncs  # per-file payload fsyncs actually happened

    def test_injected_fsync_fault_fails_clean(self, tmp_path,
                                              monkeypatch):
        """A fault in the new durability window (fail_commit_at-style:
        after the array write, around the rename) must fail the save
        cleanly — tmp swept, prior steps loadable."""
        from apex_tpu.resilience import manager as mgr_mod

        m = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
        template = capture(0, {"w": jnp.arange(4.0)}, None)
        m.save(capture(2, {"w": jnp.full((4,), 2.0)}, None),
               blocking=True)

        def flaky(p):
            raise ChaosError("injected fsync fault")

        monkeypatch.setattr(mgr_mod, "fsync_tree", flaky)
        with pytest.raises(ChaosError):
            m.save(capture(4, {"w": jnp.full((4,), 4.0)}, None),
                   blocking=True)
        monkeypatch.undo()
        leftovers = [n for n in os.listdir(m.root) if ".tmp-" in n]
        assert leftovers == []
        restored = m.restore(template)
        assert restored.step == 2
        assert float(restored.params["w"][0]) == 2.0


# ---------------------------------------------------------------------------
# satellite: multi-writer-safe stale-tmp sweep (seeded-violation reds)
# ---------------------------------------------------------------------------
@pytest.fixture
def live_foreign_pid():
    """A real live process that is NOT us — the concurrent fake host
    whose in-flight save a sweep must never delete."""
    proc = subprocess.Popen([sys.executable, "-c",
                             "import time; time.sleep(60)"])
    yield proc.pid
    proc.kill()
    proc.wait()


def _dead_pid():
    proc = subprocess.Popen([sys.executable, "-c", "pass"])
    proc.wait()
    return proc.pid


class TestMultiWriterSweep:
    def test_base_sweep_spares_live_writer(self, tmp_path,
                                           live_foreign_pid):
        """Seeded violation: a live concurrent host's in-flight
        ``step_*.tmp-<pid>`` staging tree survives a restarting peer's
        init sweep; a dead writer's is reclaimed."""
        root = tmp_path / "ckpt"
        live = root / f"step_00000006.tmp-{live_foreign_pid}"
        dead = root / f"step_00000008.tmp-{_dead_pid()}"
        for d in (live, dead):
            d.mkdir(parents=True)
            (d / "sentinel").write_text("x")
        CheckpointManager(str(root))
        assert live.exists(), \
            "sweep deleted a LIVE concurrent writer's in-flight save"
        assert not dead.exists()

    def test_elastic_sweep_spares_live_shard_writer(self, tmp_path,
                                                    live_foreign_pid):
        root = tmp_path / "ckpt"
        step = root / "step_00000004"
        live_tmp = step / f"shard-1.tmp-{live_foreign_pid}"
        dead_tmp = step / f"shard-2.tmp-{_dead_pid()}"
        for d in (live_tmp, dead_tmp):
            d.mkdir(parents=True)
        ElasticCheckpointManager(str(root), host=0, world=2)
        assert live_tmp.exists(), \
            "sweep deleted a LIVE host's in-flight shard staging"
        assert not dead_tmp.exists()

    def test_elastic_sweep_markerless_garbage_rules(self, tmp_path,
                                                    live_foreign_pid):
        root = str(tmp_path / "ckpt")
        # newest committed step: 6
        _save_world(root, _fresh_state(2, step=6, position=6), 2)

        def seed_partial(step, pid):
            d = tmp_path / "ckpt" / f"step_{step:08d}" / "shard-0.part"
            d.mkdir(parents=True)
            (d / "meta.json").write_text(json.dumps(
                {"step": step, "host": 0, "world": 2, "pid": pid}))
            return d.parent

        older_dead = seed_partial(2, _dead_pid())
        older_live = seed_partial(4, live_foreign_pid)
        newer_dead = seed_partial(8, _dead_pid())
        # a markerless OLD step holding ONLY a live peer's phase-1
        # staging (no .part yet): deadness must consider the tmp's
        # filename pid, not just .part metas
        older_live_tmp = (tmp_path / "ckpt" / "step_00000003"
                          / f"shard-1.tmp-{live_foreign_pid}")
        older_live_tmp.mkdir(parents=True)
        ElasticCheckpointManager(root, host=1, world=2)
        assert not older_dead.exists()  # garbage: old + dead writers
        assert older_live.exists(), \
            "sweep deleted a step a LIVE writer is still saving"
        assert older_live_tmp.exists(), \
            "sweep deleted a step a LIVE writer is still STAGING into"
        # >= newest commit: a live world may be (re)writing it
        assert newer_dead.exists()


# ---------------------------------------------------------------------------
# ChaosHost + Heartbeat
# ---------------------------------------------------------------------------
class TestChaosHost:
    def test_spec_roundtrip(self):
        c = (ChaosHost().kill_at_step(7).kill_in_shard_write_at(6)
             .kill_in_barrier_at(5).wedge_heartbeat_at(9, 2.5))
        assert ChaosHost.parse(c.to_spec()).to_spec() == c.to_spec()
        assert ChaosHost.parse("").to_spec() == ""
        with pytest.raises(ValueError, match="unknown chaos fault"):
            ChaosHost.parse("explode@3")

    def test_take_fires_once_at_or_past_step(self, monkeypatch):
        died = []
        monkeypatch.setattr(ChaosHost, "_die",
                            staticmethod(lambda: died.append(True)))
        c = ChaosHost().kill_at_step(5)
        c.at_step_boundary(4)
        assert not died
        c.at_step_boundary(6)  # past the armed step still fires
        assert len(died) == 1
        c.at_step_boundary(7)  # once only
        assert len(died) == 1
        assert c.faults_fired == [("kill", 6)]

    def test_wedge_take(self):
        c = ChaosHost().wedge_heartbeat_at(3, 1.5)
        assert c.take_wedge(2) is None
        assert c.take_wedge(3) == 1.5
        assert c.take_wedge(4) is None


class TestHeartbeat:
    def test_beat_read_age(self, tmp_path):
        path = str(tmp_path / "hb" / "hb-1")
        assert Heartbeat.age_s(path) is None
        hb = Heartbeat(path, host=1)
        hb.beat(7)
        rec = Heartbeat.read(path)
        assert rec["host"] == 1 and rec["step"] == 7
        assert Heartbeat.age_s(path) < 5.0


# ---------------------------------------------------------------------------
# the supervisor (non-jax children: fast)
# ---------------------------------------------------------------------------
def _script_host(tmp_path, body_by_incarnation):
    """build_cmd for tiny non-jax hosts: each incarnation runs the
    python -c body chosen for it (formatted with host/heartbeat)."""

    def build_cmd(host, world, incarnation):
        body = body_by_incarnation[min(incarnation,
                                       len(body_by_incarnation) - 1)]
        hb = os.path.join(str(tmp_path / "hb"), f"hb-{host}")
        return [sys.executable, "-c", body.format(hb=hb, host=host)]

    return build_cmd


BEAT_AND_EXIT0 = "open(r'{hb}', 'w').close()"
BEAT_AND_DIE = ("import sys; open(r'{hb}', 'w').close(); "
                "sys.exit(3 if {host} == 1 else 0)")
BEAT_AND_HANG = ("import time; open(r'{hb}', 'w').close(); "
                 "time.sleep(60 if {host} == 1 else 0)")


class TestSupervisor:
    def test_death_restart_and_recovery(self, tmp_path):
        rec = RingBufferRecorder()
        sup = Supervisor(
            _script_host(tmp_path, [BEAT_AND_DIE, BEAT_AND_EXIT0]),
            2, heartbeat_dir=str(tmp_path / "hb"),
            heartbeat_timeout_s=30.0, max_restarts=2, sink=rec)
        summary = sup.run()
        assert summary["ok"] and summary["restarts"] == 1
        inc = summary["incidents"][0]
        assert inc["kind"] == "host_death" and inc["host"] == 1
        assert inc["recovery_s"] is not None
        events = [r["event"] for r in rec.records]
        assert "host_death" in events and "world_restart" in events
        death = next(r for r in rec.records
                     if r["event"] == "host_death")
        assert death["host"] == 1 and death["rank"] == 1

    def test_hang_detection_kills_and_restarts(self, tmp_path):
        rec = RingBufferRecorder()
        sup = Supervisor(
            _script_host(tmp_path, [BEAT_AND_HANG, BEAT_AND_EXIT0]),
            2, heartbeat_dir=str(tmp_path / "hb"),
            heartbeat_timeout_s=0.4, poll_s=0.02,
            max_restarts=2, sink=rec)
        t0 = time.monotonic()
        summary = sup.run()
        assert summary["ok"] and summary["restarts"] == 1
        assert summary["incidents"][0]["kind"] == "host_hang"
        assert summary["incidents"][0]["host"] == 1
        assert time.monotonic() - t0 < 30.0  # hung host was KILLED

    def test_max_restarts_raises_world_failed(self, tmp_path):
        sup = Supervisor(
            _script_host(tmp_path, [BEAT_AND_DIE]),
            2, heartbeat_dir=str(tmp_path / "hb"),
            max_restarts=1)
        with pytest.raises(WorldFailedError, match="host 1"):
            sup.run()
        assert sup.restarts == 2
        assert len(sup.incidents) == 2

    def test_reshape_on_restart(self, tmp_path):
        sup = Supervisor(
            _script_host(tmp_path, [BEAT_AND_DIE, BEAT_AND_EXIT0]),
            4, heartbeat_dir=str(tmp_path / "hb"), max_restarts=2,
            on_restart=lambda incarnation, world: 2)
        summary = sup.run()
        assert summary["ok"]
        assert summary["world_history"] == [4, 2]


# ---------------------------------------------------------------------------
# satellite: attributable hang events
# ---------------------------------------------------------------------------
class TestWatchdogContext:
    def test_ctor_context_tags_hang_events(self):
        rec = RingBufferRecorder()
        with HangWatchdog(timeout_s=0.1, poll_s=0.02, sink=rec,
                          context={"host": 3, "rank": 3}) as wd:
            with pytest.raises(HangError):
                wd.wait(threading.Event(), "supervised barrier")
        (hang,) = [r for r in rec.records if r["event"] == "hang"]
        assert hang["host"] == 3 and hang["rank"] == 3

    def test_per_call_context_wins(self):
        rec = RingBufferRecorder()
        with HangWatchdog(timeout_s=0.1, poll_s=0.02, sink=rec,
                          context={"host": 3, "step": 1}) as wd:
            with pytest.raises(HangError):
                wd.wait(threading.Event(), "supervised barrier",
                        context={"step": 9})
        (hang,) = [r for r in rec.records if r["event"] == "hang"]
        assert hang["host"] == 3 and hang["step"] == 9


# ---------------------------------------------------------------------------
# CLI wiring
# ---------------------------------------------------------------------------
class TestSupervisorCLI:
    def test_parse_chaos_and_reshape(self):
        from tools import elastic_supervisor as es

        assert es.parse_chaos(["0:2:kill@7", "1:0:wedge@3:9"]) == {
            (0, 2): "kill@7", (1, 0): "wedge@3:9"}
        assert es.parse_reshape(["1:2", "3:1"]) == {1: 2, 3: 1}
        with pytest.raises(SystemExit):
            es.parse_chaos(["bogus"])
        with pytest.raises(SystemExit):
            es.parse_reshape(["bogus"])

    def test_host_program_exists(self):
        from tools import elastic_supervisor as es

        assert os.path.exists(es.HOST_PROGRAM)


class TestCheckWiring:
    def test_resilience_check_gained_elastic_legs(self):
        from tools import resilience_check

        assert "elastic_resume" in resilience_check.CHECKS
        assert "host_kill" in resilience_check.CHECKS


# ---------------------------------------------------------------------------
# the full chaos trace (slow tier; tier-1 coverage rides the CLI legs)
# ---------------------------------------------------------------------------
HOST_PROGRAM = str(REPO / "apex_tpu" / "resilience" / "_elastic_host.py")


def test_chaos_trace_kills_reshapes_byte_exact(tmp_path):
    """The acceptance chaos proof: a supervised 4-fake-host run suffers
    a SIGKILL mid-``.part``-write, restarts, RESHAPES to 2 hosts,
    suffers a heartbeat wedge (hang) and a SIGKILL mid-barrier, and
    still lands loss records byte-identical to an uninterrupted run —
    no markerless step is ever restored (a torn restore would diverge
    the records)."""
    steps, save_every = 14, 2
    run = tmp_path
    ckpt = str(run / "ckpt")
    losses = str(run / "losses.txt")
    chaos_by = {  # (incarnation, host) -> spec
        (0, 2): "kill_write@5",   # SIGKILL mid-.part write
        (1, 1): "wedge@8",        # heartbeat wedge -> host_hang
        (2, 0): "kill_barrier@10",  # SIGKILL mid commit barrier
    }

    def build_cmd(host, world, incarnation):
        return [sys.executable, HOST_PROGRAM,
                "--host", host, "--world", world, "--steps", steps,
                "--root", ckpt, "--losses", losses,
                "--heartbeat-dir", str(run / "hb"),
                "--save-every", save_every, "--barrier-timeout", 30,
                "--step-sleep", 0.1]

    def host_env(host, world, incarnation):
        env = {"PYTHONPATH": str(REPO) + os.pathsep
               + os.environ.get("PYTHONPATH", ""),
               "JAX_PLATFORMS": "cpu"}
        spec = chaos_by.get((incarnation, host))
        if spec:
            env["APEX_TPU_ELASTIC_CHAOS"] = spec
        return env

    rec = RingBufferRecorder()
    # heartbeat timeout must clear the first step's COMPILE window (a
    # cold host legitimately goes several seconds between its startup
    # beat and its first post-step beat) while staying far under the
    # wedge's stall — 10s does both on the CPU harness
    sup = Supervisor(
        build_cmd, 4, heartbeat_dir=str(run / "hb"),
        heartbeat_timeout_s=10.0, startup_timeout_s=120.0,
        poll_s=0.05, max_restarts=4,
        sink=rec, host_env=host_env,
        on_restart=lambda incarnation, world: 2 if incarnation == 0
        else world)
    summary = sup.run()
    assert summary["ok"], summary
    assert summary["restarts"] == 3
    assert summary["world_history"] == [4, 2, 2, 2]
    kinds = [i["kind"] for i in summary["incidents"]]
    assert kinds == ["host_death", "host_hang", "host_death"]

    records = {}
    with open(losses) as f:
        for line in f:
            if line.startswith("S "):
                _, s, hexval = line.split()
                step = int(s)
                if step in records:  # replays must also be identical
                    assert records[step] == hexval, \
                        f"replay diverged at step {step}"
                records[step] = hexval
    ref, _ = reference_records(2, steps)
    assert records == ref  # byte-exact final loss records
