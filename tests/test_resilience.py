"""apex_tpu.resilience: fault-tolerant training machinery.

Covers the four pillars (ISSUE 5): preemption-safe checkpointing
(atomic writes, retention, async barrier, corruption fallback,
SIGTERM emergency flush), resumable TrainState, last-good rewind, and
the hang watchdog — each exercised through the chaos harness
(``apex_tpu.resilience.chaos``), plus the promoted retry policy and the
``tools/resilience_check.py --self`` CI smoke (the tier-1 wiring, like
``static_audit --self``). The subprocess crash/resume bit-exactness
test lives in ``tests/test_crash_resume.py``.
"""
import json
import os
import signal
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from apex_tpu.amp.scaler import LossScaler  # noqa: E402
from apex_tpu.checkpoint import (  # noqa: E402
    CheckpointCorruptError, load_checkpoint, save_checkpoint,
)
from apex_tpu.optimizers import FusedAdam  # noqa: E402
from apex_tpu.resilience import (  # noqa: E402
    ChaosError,
    ChaosMonkey,
    CheckpointManager,
    HangError,
    HangWatchdog,
    IndexedBatches,
    ResumableIterator,
    RetryPolicy,
    RewindController,
    RewindExhaustedError,
    StallingSink,
    capture,
    corrupt_checkpoint,
    poison_grads,
    resume_or_init,
    retry_call,
    send_preemption,
)
from apex_tpu import telemetry  # noqa: E402
from apex_tpu.telemetry import numerics as tnum  # noqa: E402
from tools import resilience_check  # noqa: E402


# ---------------------------------------------------------------------------
# retry.py
# ---------------------------------------------------------------------------
class TestRetry:
    def test_success_no_retry(self):
        calls = []
        assert retry_call(lambda: calls.append(1) or 42,
                          policy=RetryPolicy(attempts=3)) == 42
        assert len(calls) == 1

    def test_non_transient_surfaces_immediately(self):
        calls = []

        def boom():
            calls.append(1)
            raise ValueError("real failure")

        policy = RetryPolicy(attempts=4, retry_on=(OSError,))
        with pytest.raises(ValueError):
            retry_call(boom, policy=policy)
        assert len(calls) == 1

    def test_transient_retries_then_succeeds_with_telemetry(self):
        calls, events = [], []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("storage blip")
            return "ok"

        policy = RetryPolicy(attempts=4, retry_on=(OSError,),
                             base_delay=0.01, max_delay=0.02)
        slept = []
        out = retry_call(flaky, policy=policy, tag="t",
                         sink=events.append, sleep=slept.append)
        assert out == "ok" and len(calls) == 3
        assert [e["event"] for e in events] == ["retry", "retry"]
        assert events[0]["attempt"] == 1 and events[0]["of"] == 4
        assert "OSError" in events[0]["error"]
        # jittered exponential: each delay bounded by base * 2^k
        assert len(slept) == 2
        assert 0.0 <= slept[0] <= 0.01 and 0.0 <= slept[1] <= 0.02

    def test_exhausted_attempts_raise_last(self):
        policy = RetryPolicy(attempts=2, retry_on=(OSError,))
        with pytest.raises(OSError):
            retry_call(lambda: (_ for _ in ()).throw(OSError("x")),
                       policy=policy)

    def test_message_filter_narrows_the_class_match(self):
        # class AND message must match
        policy = RetryPolicy(attempts=3, retry_on=(Exception,),
                             message_filter=lambda e: "busy" in str(e))
        assert policy.is_transient(Exception("device busy, try again"))
        assert not policy.is_transient(Exception("invalid argument"))

    def test_zero_base_delay_never_sleeps(self):
        policy = RetryPolicy(attempts=3, retry_on=(OSError,))
        slept = []
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 2:
                raise OSError("blip")
            return 1

        retry_call(flaky, policy=policy, sleep=slept.append)
        assert slept == []

    def test_deadline_bounds_total_retry_time(self):
        """ISSUE-10 satellite: `deadline=` is an overall wall-clock
        budget across all attempts — when elapsed + the next backoff
        would cross it, the loop gives up early (a retry_deadline
        event, the last exception surfaces) even with attempts left."""
        calls, events, slept = [], [], []
        t = {"now": 0.0}

        def fake_sleep(d):
            slept.append(d)
            t["now"] += d

        def always_fails():
            calls.append(1)
            t["now"] += 0.4  # each attempt burns 0.4s of fake time
            raise OSError("down")

        policy = RetryPolicy(attempts=10, retry_on=(OSError,),
                             base_delay=1.0, max_delay=1.0,
                             deadline=2.0,
                             rng=__import__("random").Random(0))
        with pytest.raises(OSError):
            retry_call(always_fails, policy=policy, sink=events.append,
                       sleep=fake_sleep, clock=lambda: t["now"])
        # far fewer than 10 attempts: the budget cut it off
        assert 1 <= len(calls) < 10
        assert events[-1]["event"] == "retry_deadline"
        assert events[-1]["deadline_s"] == 2.0
        assert t["now"] < 2.0 + 1.0  # never slept past the budget

    def test_deadline_none_keeps_attempt_count_semantics(self):
        """No deadline: the historical attempts-only behaviour, every
        attempt runs."""
        calls = []
        policy = RetryPolicy(attempts=3, retry_on=(OSError,))
        with pytest.raises(OSError):
            retry_call(lambda: calls.append(1) or
                       (_ for _ in ()).throw(OSError("x")),
                       policy=policy)
        assert len(calls) == 3

    def test_deadline_not_crossed_retries_normally(self):
        """A roomy deadline changes nothing: transient retries proceed
        and succeed."""
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("blip")
            return "ok"

        policy = RetryPolicy(attempts=5, retry_on=(OSError,),
                             base_delay=0.001, deadline=60.0)
        assert retry_call(flaky, policy=policy) == "ok"
        assert len(calls) == 3


# ---------------------------------------------------------------------------
# checkpoint.py hardening (satellite)
# ---------------------------------------------------------------------------
class TestCheckpointHardening:
    def test_atomic_save_failure_keeps_previous(self, tmp_path):
        p = str(tmp_path / "ck")
        save_checkpoint(p, {"w": jnp.arange(4.0)})

        class Unserializable:
            pass

        with pytest.raises(Exception):
            save_checkpoint(p, {"w": Unserializable()})
        # the failed write neither clobbered the old tree nor left tmp
        back = load_checkpoint(p)
        np.testing.assert_array_equal(np.asarray(back["w"]),
                                      np.arange(4.0))
        assert not [d for d in os.listdir(tmp_path) if ".tmp-" in d]

    def test_truncated_checkpoint_raises_typed_error(self, tmp_path):
        p = str(tmp_path / "ck")
        state = {"w": jnp.arange(64.0)}
        save_checkpoint(p, state)
        corrupt_checkpoint(p)
        with pytest.raises(CheckpointCorruptError) as ei:
            load_checkpoint(p, target=state)
        assert ei.value.path == os.path.abspath(p)
        assert ei.value.__cause__ is not None

    def test_missing_checkpoint_is_file_not_found(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            load_checkpoint(str(tmp_path / "nope"))

    def test_overwrite_false_refuses_before_writing(self, tmp_path):
        p = str(tmp_path / "ck")
        save_checkpoint(p, {"w": jnp.zeros(2)})
        with pytest.raises(FileExistsError):
            save_checkpoint(p, {"w": jnp.ones(2)}, overwrite=False)
        # it failed BEFORE staging: no tmp tree was created
        assert not [d for d in os.listdir(tmp_path) if ".tmp-" in d]

    def test_dead_writer_tmp_swept_on_next_save(self, tmp_path):
        """A crashed previous process's full-size partial tree (pid in
        the name, writer gone) is cleaned by the next save."""
        p = str(tmp_path / "ck")
        dead = f"{p}.tmp-999999999"  # no such pid
        os.makedirs(dead)
        save_checkpoint(p, {"w": jnp.zeros(2)})
        assert not os.path.exists(dead)
        assert os.path.exists(p)


# ---------------------------------------------------------------------------
# CheckpointManager (tentpole pillar 1)
# ---------------------------------------------------------------------------
def _mini_state(step, fill, *, opt=None, params=None):
    params = params if params is not None else {
        "w": jnp.full((8,), float(fill), jnp.bfloat16),
        "b": jnp.full((4,), float(fill), jnp.float32)}
    opt_state = opt.init(params) if opt is not None else None
    return capture(step, params, opt_state, data={"position": step})


class TestCheckpointManager:
    def test_save_restore_roundtrip_with_packed_state(self, tmp_path):
        opt = FusedAdam(lr=1e-2, packed=True, packed_interpret=True,
                        packed_chunk_size=256, master_weights=True)
        sc = LossScaler("dynamic")
        params = {"w": jnp.arange(8.0, dtype=jnp.bfloat16),
                  "b": jnp.ones((4,), jnp.float32)}
        opt_state = opt.init(params)
        g = jax.tree_util.tree_map(jnp.ones_like, params)
        params2, opt_state2 = opt.step(g, opt_state, params)
        sstate = sc.init_state()._replace(loss_scale=jnp.float32(512.0),
                                          consecutive_skips=jnp.int32(2))
        mon = tnum.NumericsMonitor(params)
        metrics = telemetry.accumulate(telemetry.init_metrics(),
                                       loss=jnp.float32(1.5), tokens=8)
        rng = jax.random.PRNGKey(7)
        st = capture(5, params2, opt_state2, scaler=sstate, rng=rng,
                     data={"position": 5}, metrics=metrics,
                     numerics=mon.init())
        mgr = CheckpointManager(str(tmp_path), keep_n=3)
        mgr.save(st, blocking=True)

        def init_fn():
            return capture(0, params, opt.init(params),
                           scaler=sc.init_state(),
                           rng=jax.random.PRNGKey(0),
                           data={"position": 0},
                           metrics=telemetry.init_metrics(),
                           numerics=mon.init())

        back, resumed = resume_or_init(mgr, init_fn)
        assert resumed and back.step == 5
        assert back.data == {"position": 5}
        # bit-exact across every leaf, packed flat buffers included
        for a, b in zip(jax.tree_util.tree_leaves((st.params, st.opt_state,
                                                   st.scaler, st.rng,
                                                   st.metrics)),
                        jax.tree_util.tree_leaves((back.params,
                                                   back.opt_state,
                                                   back.scaler, back.rng,
                                                   back.metrics))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(back.scaler.loss_scale) == 512.0
        assert int(back.scaler.consecutive_skips) == 2

    def test_retention_gc_keeps_newest(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_n=2)
        for s in (1, 2, 3, 4, 5):
            mgr.save(_mini_state(s, s))
        mgr.wait_until_finished()
        assert mgr.all_steps() == [4, 5]

    def test_emergency_checkpoints_survive_gc(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_n=1)
        mgr.save(_mini_state(1, 1), blocking=True, emergency=True)
        for s in (2, 3, 4):
            mgr.save(_mini_state(s, s), blocking=True)
        assert 1 in mgr.all_steps() and 4 in mgr.all_steps()

    def test_emergency_save_is_always_blocking(self, tmp_path):
        # a non-blocking emergency would clobber the single-slot async
        # tracking of the in-flight save it deliberately skipped the
        # barrier for — loud error, not a silent race
        mgr = CheckpointManager(str(tmp_path), async_save=True)
        with pytest.raises(ValueError, match="always blocking"):
            mgr.save(_mini_state(1, 1), blocking=False, emergency=True)
        mgr.save(_mini_state(1, 1), emergency=True)  # sync despite async_save
        assert mgr.all_steps() == [1]  # committed with no barrier needed

    def test_restore_explicit_missing_step_raises(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_n=2)
        for s in (3, 6):
            mgr.save(_mini_state(s, s), blocking=True)
        with pytest.raises(FileNotFoundError, match=r"step 9.*\[3, 6\]"):
            mgr.restore(_mini_state(0, 0), step=9)
        # in-range explicit step still restores
        assert mgr.restore(_mini_state(0, 0), step=3).step == 3

    def test_maybe_save_cadence(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), save_every=3)
        saved = [s for s in range(10)
                 if mgr.maybe_save(_mini_state(s, s))]
        mgr.wait_until_finished()
        assert saved == [3, 6, 9]
        assert mgr.all_steps() == [3, 6, 9][-mgr.keep_n:]

    def test_maybe_save_every_step_skips_step_zero(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / "a"), save_every=1)
        saved = [s for s in range(4)
                 if mgr.maybe_save(_mini_state(s, s))]
        mgr.wait_until_finished()
        assert saved == [1, 2, 3]  # never the uninitialized step 0
        # save_every=0: every call, step 0 included
        mgr0 = CheckpointManager(str(tmp_path / "b"), save_every=0)
        assert mgr0.maybe_save(_mini_state(0, 0))
        mgr0.wait_until_finished()

    def test_async_failed_write_surfaces_at_barrier(self, tmp_path):
        chaos = ChaosMonkey().fail_write_at(2)
        rec = telemetry.RingBufferRecorder()
        mgr = CheckpointManager(str(tmp_path), chaos=chaos, sink=rec)
        mgr.save(_mini_state(2, 2))  # async; fails in the background
        with pytest.raises(ChaosError):
            mgr.wait_until_finished()
        assert "checkpoint_failed" in [r["event"] for r in rec.records]

    def test_failed_commit_leaves_previous_loadable(self, tmp_path):
        """The atomicity acceptance: a write failed mid-flight (after
        the array tree, before the rename) leaves the previous
        checkpoint fully loadable and the failed step invisible."""
        chaos = ChaosMonkey().fail_commit_at(4)
        mgr = CheckpointManager(str(tmp_path), chaos=chaos)
        mgr.save(_mini_state(2, 2), blocking=True)
        with pytest.raises(ChaosError):
            mgr.save(_mini_state(4, 4), blocking=True)
        assert mgr.all_steps() == [2]
        back = mgr.restore(_mini_state(0, 0))
        assert back.step == 2
        assert float(np.asarray(back.params["b"])[0]) == 2.0

    def test_corrupt_newest_falls_back_to_good(self, tmp_path):
        rec = telemetry.RingBufferRecorder()
        mgr = CheckpointManager(str(tmp_path), sink=rec)
        for s in (2, 4, 6):
            mgr.save(_mini_state(s, s), blocking=True)
        corrupt_checkpoint(str(tmp_path / "step_00000006"))
        corrupt_checkpoint(str(tmp_path / "step_00000004"))
        back = mgr.restore(_mini_state(0, 0))
        assert back.step == 2
        falls = [r for r in rec.records
                 if r["event"] == "checkpoint_fallback"]
        assert [f["step"] for f in falls] == [6, 4]

    def test_restore_none_when_empty(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        assert mgr.restore(_mini_state(0, 0)) is None
        st, resumed = resume_or_init(mgr, lambda: _mini_state(0, 0))
        assert not resumed and st.step == 0

    def test_all_checkpoints_failing_raises_not_reinit(self, tmp_path):
        """Checkpoints exist but none loads (here: a template whose
        structure no longer matches) — that must be a loud error, not a
        silent walk-off-the-end that lets resume_or_init restart the
        run from step 0."""
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(_mini_state(2, 2), blocking=True)
        bigger = capture(0, {"w": jnp.zeros((8,), jnp.bfloat16),
                             "b": jnp.zeros((4,)),
                             "extra": jnp.zeros((2,))},
                         None, data={"position": 0})
        rec = []
        mgr._record = rec.append
        with pytest.raises(CheckpointCorruptError):
            mgr.restore(bigger)
        assert rec and rec[0]["event"] == "checkpoint_fallback"

    def test_preemption_handler_flushes_emergency(self, tmp_path):
        rec = telemetry.RingBufferRecorder()
        mgr = CheckpointManager(str(tmp_path), keep_n=2, sink=rec)
        state_holder = {"state": _mini_state(7, 7)}
        mgr.install_preemption_handler(lambda: state_holder["state"])
        try:
            assert not mgr.preempted
            send_preemption(signal.SIGTERM)
            # handler runs synchronously in the main thread
            assert mgr.preempted
            assert 7 in mgr.all_steps()
            with open(tmp_path / "step_00000007" / "meta.json") as f:
                assert json.load(f)["emergency"] is True
            events = [r["event"] for r in rec.records]
            assert "preemption" in events and "checkpoint_saved" in events
        finally:
            mgr.uninstall_preemption_handler()
        # handler restored: SIGTERM handling back to whatever it was
        assert signal.getsignal(signal.SIGTERM) is not None

    def test_wait_bounded_by_watchdog(self, tmp_path):
        wd = HangWatchdog(timeout_s=0.3, poll_s=0.02)
        mgr = CheckpointManager(str(tmp_path), watchdog=wd)
        mgr._done.clear()  # simulate a wedged background write
        try:
            with pytest.raises(HangError) as ei:
                mgr.wait_until_finished()
            assert "wait_until_finished" in str(ei.value)
            assert "MainThread" in ei.value.stacks
        finally:
            mgr._done.set()
            wd.close()


# ---------------------------------------------------------------------------
# resumable iteration
# ---------------------------------------------------------------------------
class TestResumableIteration:
    def test_indexed_batches_roundtrip(self):
        it = IndexedBatches(lambda i: i * 10)
        assert [next(it) for _ in range(3)] == [0, 10, 20]
        st = it.state()
        it2 = IndexedBatches(lambda i: i * 10, position=st["position"])
        assert next(it2) == 30
        it2.skip(2)
        assert next(it2) == 60

    def test_iterator_drain_restore(self):
        it = ResumableIterator(lambda: iter(range(100)))
        assert [next(it) for _ in range(4)] == [0, 1, 2, 3]
        st = it.state()
        it.restore(st)
        assert next(it) == 4
        it.skip(5)
        assert next(it) == 10


# ---------------------------------------------------------------------------
# scaler consecutive-skip counter (satellite) + scaler_stall rule
# ---------------------------------------------------------------------------
class TestScalerStall:
    def test_consecutive_skips_counter(self):
        sc = LossScaler("dynamic", hysteresis=1)
        st = sc.init_state()
        for expect in (1, 2, 3):
            st = sc.update_scale(
                st._replace(found_inf=jnp.asarray(True)))
            assert int(st.consecutive_skips) == expect
        st = sc.update_scale(st)  # clean step resets the run
        assert int(st.consecutive_skips) == 0

    def test_static_scaler_also_counts(self):
        sc = LossScaler(128.0)
        st = sc.update_scale(
            sc.init_state()._replace(found_inf=jnp.asarray(True)))
        assert int(st.consecutive_skips) == 1

    def test_state_dict_roundtrip_includes_counter(self):
        sc = LossScaler("dynamic")
        st = sc.init_state()._replace(consecutive_skips=jnp.int32(5))
        sd = sc.state_dict(st)
        assert sd["consecutive_skips"] == 5
        back = sc.load_state_dict(sd)
        assert int(back.consecutive_skips) == 5
        # legacy dicts without the key load as zero
        del sd["consecutive_skips"]
        assert int(sc.load_state_dict(sd).consecutive_skips) == 0

    def test_scaler_stall_event_edge_triggered(self):
        """Past max_consecutive_skips the anomaly engine emits ONE
        scaler_stall (the rewind trigger) — not one per further skip."""
        params = {"w": jnp.ones((4,))}
        sc = LossScaler("dynamic", hysteresis=1)
        mon = tnum.NumericsMonitor(params, max_consecutive_skips=3)
        rec = telemetry.RingBufferRecorder()
        st, ns = sc.init_state(), mon.init()
        for _ in range(6):  # six consecutive overflowed updates
            st, ns = sc.update_scale(
                st._replace(found_inf=jnp.asarray(True)), numerics=ns)
            ns = mon.drain(ns, rec)
        jax.effects_barrier()
        stalls = [r for r in rec.records if r.get("kind") == "scaler_stall"]
        assert len(stalls) == 1
        assert stalls[0]["consecutive_skips"] == 3
        assert stalls[0]["max_consecutive_skips"] == 3
        # recovery then a second stall re-arms the edge
        st, ns = sc.update_scale(st, numerics=ns)  # clean
        ns = mon.drain(ns, rec)
        for _ in range(4):
            st, ns = sc.update_scale(
                st._replace(found_inf=jnp.asarray(True)), numerics=ns)
            ns = mon.drain(ns, rec)
        jax.effects_barrier()
        stalls = [r for r in rec.records if r.get("kind") == "scaler_stall"]
        assert len(stalls) == 2

    def test_stall_disabled_with_zero_budget(self):
        params = {"w": jnp.ones((4,))}
        sc = LossScaler("dynamic", hysteresis=1)
        mon = tnum.NumericsMonitor(params, max_consecutive_skips=0)
        rec = telemetry.RingBufferRecorder()
        st, ns = sc.init_state(), mon.init()
        for _ in range(5):
            st, ns = sc.update_scale(
                st._replace(found_inf=jnp.asarray(True)), numerics=ns)
            ns = mon.drain(ns, rec)
        jax.effects_barrier()
        assert not [r for r in rec.records
                    if r.get("kind") == "scaler_stall"]


# ---------------------------------------------------------------------------
# rewind (tentpole pillar 3)
# ---------------------------------------------------------------------------
class TestRewind:
    def test_ring_and_budget_trigger(self):
        ctl = RewindController(keep=2, skip_budget=3, snapshot_every=2)
        for s in (1, 2, 3, 4, 5, 6):
            ctl.offer(_mini_state(s, s), healthy=True)
        # snapshot_every=2 spacing, keep=2 -> ring holds {3, 5}
        assert [sn.step for sn in ctl._ring] == [3, 5]
        ctl.offer(_mini_state(7, 7),
                  consecutive_skips=jnp.int32(3))
        assert ctl.rewind_pending

    def test_anomaly_event_sink_triggers(self):
        ctl = RewindController()
        ctl.record({"event": "anomaly", "kind": "grad_spike"})
        assert not ctl.rewind_pending  # spikes alone do not rewind
        ctl.record({"event": "anomaly", "kind": "scaler_stall"})
        assert ctl.rewind_pending

    def test_rewind_restores_and_advances_data(self):
        rec = telemetry.RingBufferRecorder()
        ctl = RewindController(keep=2, recorder=rec)
        st = capture(4, {"w": jnp.full((4,), 4.0)}, None,
                     data={"position": 4})
        ctl.offer(st, healthy=True)
        it = IndexedBatches(lambda i: i, position=9)
        ctl.request_rewind("test trigger")
        back = ctl.rewind(data_iter=it, skip_batches=2, current_step=9)
        assert int(back.step) == 4
        np.testing.assert_array_equal(np.asarray(back.params["w"]),
                                      np.full((4,), 4.0))
        # the data stream does NOT rewind: current position + margin
        assert back.data == {"position": 11}
        assert not ctl.rewind_pending
        ev = [r for r in rec.records if r["event"] == "rewind"]
        assert len(ev) == 1
        assert ev[0]["to_step"] == 4 and ev[0]["step"] == 9
        assert ev[0]["trigger"] == "test trigger"

    def test_snapshot_is_donation_safe_copy(self):
        ctl = RewindController()
        w = jnp.arange(4.0)
        st = capture(1, {"w": w}, None)
        ctl.offer(st, healthy=True)
        snap_w = ctl._ring[0].state.params["w"]
        assert isinstance(snap_w, np.ndarray)
        # mutating the snapshot cannot touch the live array and vice versa
        snap_w[0] = 99.0
        assert float(w[0]) == 0.0

    def test_max_rewinds_exhausts(self):
        ctl = RewindController(max_rewinds=1)
        ctl.offer(_mini_state(1, 1), healthy=True)
        ctl.rewind()
        with pytest.raises(RewindExhaustedError):
            ctl.rewind()

    def test_rewind_without_snapshot_raises(self):
        with pytest.raises(RuntimeError):
            RewindController().rewind()

    def test_poison_grads_in_jit(self):
        grads = {"w": jnp.ones((4,), jnp.bfloat16)}

        @jax.jit
        def f(g, p):
            return poison_grads(g, p)

        clean = f(grads, False)
        np.testing.assert_array_equal(np.asarray(clean["w"], np.float32),
                                      np.ones(4))
        assert not np.any(np.isfinite(np.asarray(f(grads, True)["w"],
                                                 np.float32)))


# ---------------------------------------------------------------------------
# watchdog (tentpole pillar 4)
# ---------------------------------------------------------------------------
class TestWatchdog:
    def test_wait_completes_when_ready(self):
        with HangWatchdog(timeout_s=5.0, poll_s=0.01) as wd:
            ev = threading.Event()
            threading.Timer(0.05, ev.set).start()
            wd.wait(ev, "quick")  # returns, no raise
            assert wd.trips == 0

    def test_wait_trips_with_stack_dump_and_event(self):
        rec = telemetry.RingBufferRecorder()
        with HangWatchdog(timeout_s=0.2, poll_s=0.02, sink=rec) as wd:
            with pytest.raises(HangError) as ei:
                wd.wait(threading.Event(), "stuck drain")
            assert "stuck drain" in str(ei.value)
            assert "MainThread" in ei.value.stacks
        hangs = [r for r in rec.records if r["event"] == "hang"]
        assert len(hangs) == 1 and hangs[0]["what"] == "stuck drain"
        assert "MainThread" in hangs[0]["stacks"]

    def test_wait_predicate_form(self):
        t0 = time.monotonic()
        with HangWatchdog(timeout_s=5.0, poll_s=0.01) as wd:
            wd.wait(lambda: time.monotonic() - t0 > 0.05, "predicate")

    def test_armed_block_interrupted(self):
        """A stalled callback (chaos StallingSink shape) under armed()
        raises HangError instead of hanging the run."""
        sink = StallingSink(stall_s=30.0)
        with HangWatchdog(timeout_s=0.3, poll_s=0.02) as wd:
            with pytest.raises(HangError):
                with wd.armed("stalled telemetry drain"):
                    sink.record({"event": "x"})  # blocks ~30s unwatched
        sink.release()

    def test_armed_completes_without_trip(self):
        with HangWatchdog(timeout_s=5.0, poll_s=0.01) as wd:
            with wd.armed("fast block"):
                time.sleep(0.02)
            assert wd.trips == 0


# ---------------------------------------------------------------------------
# tools/resilience_check.py (satellite: CI smoke, tier-1 wiring)
# ---------------------------------------------------------------------------
class TestResilienceCheckCLI:
    @pytest.mark.parametrize("check", sorted(resilience_check.CHECKS))
    def test_each_check_passes(self, check):
        res = resilience_check.CHECKS[check]()
        assert res["ok"], res

    def test_cli_self_exit_zero(self, capsys):
        rc = resilience_check.main(["--self", "--check", "failed_write",
                                    "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0 and out["ok"]

    def test_cli_failure_exit_one(self, monkeypatch):
        monkeypatch.setitem(resilience_check.CHECKS, "seeded_fail",
                            lambda: {"ok": False})
        assert resilience_check.main(
            ["--self", "--check", "seeded_fail"]) == 1

    def test_cli_infra_error_exit_two(self, monkeypatch):
        def boom():
            raise RuntimeError("infra")

        monkeypatch.setitem(resilience_check.CHECKS, "seeded_boom", boom)
        assert resilience_check.main(
            ["--self", "--check", "seeded_boom"]) == 2
