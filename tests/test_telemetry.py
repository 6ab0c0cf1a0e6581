"""Telemetry subsystem tests: in-jit MetricsState accumulate/drain,
recorder sinks, scaler counter wiring, bubble-fraction math, tick hooks.

Design contract pinned here: instrumentation lives INSIDE the jitted step
(device accumulators + async ``jax.debug.callback`` drains under
``lax.cond``) and adds no host syncs; window stats reset per drain while
overflow/growth counters are cumulative; the pipeline bubble accounting
must reproduce the textbook ``(p-1)/(m+p-1)`` and the 1F1B module's
documented ``(D+pp-1)/T`` fraction.
"""
import functools
import json

import jax
import jax.numpy as jnp
import pytest

from apex_tpu import telemetry


# ---------------------------------------------------------------------------
# MetricsState accumulate / drain
# ---------------------------------------------------------------------------

def test_metrics_accumulate_and_drain_every_n():
    rec = telemetry.RingBufferRecorder()
    m = telemetry.init_metrics()

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(m, loss):
        m = telemetry.accumulate(m, loss=loss, tokens=64)
        m = telemetry.drain(m, rec, every_n=3, tag="unit")
        return m, loss + 1.0

    loss = jnp.float32(1.0)
    for _ in range(7):
        m, loss = step(m, loss)
    jax.effects_barrier()

    # drains at total_steps 3 and 6 only
    assert len(rec.records) == 2
    r0, r1 = rec.records
    assert r0["step"] == 3 and r1["step"] == 6
    assert r0["steps_in_window"] == 3 and r1["steps_in_window"] == 3
    # window means: losses 1,2,3 -> 2.0; 4,5,6 -> 5.0
    assert r0["loss"] == pytest.approx(2.0)
    assert r1["loss"] == pytest.approx(5.0)
    assert r0["tag"] == "unit"
    # window tokens reset, cumulative tokens do not
    assert r0["tokens"] == pytest.approx(192.0)
    assert r1["total_tokens"] == pytest.approx(384.0)
    # second drain carries wall-dt derived rates
    assert "wall_dt_s" in r1 and r1["steps_per_sec"] > 0
    # the undrained 7th step stays in the device window
    assert int(m.window_steps) == 1 and int(m.total_steps) == 7


def test_metrics_grad_and_param_norms():
    grads = {"a": jnp.full((3,), 2.0), "b": jnp.full((4,), -2.0)}
    m = telemetry.accumulate(telemetry.init_metrics(), grads=grads,
                             params={"w": jnp.full((9,), 1.0)})
    assert float(m.grad_norm_sum) == pytest.approx((4.0 * 7) ** 0.5)
    assert float(m.param_norm_sum) == pytest.approx(3.0)
    with pytest.raises(ValueError):
        telemetry.accumulate(m, grads=grads, grad_norm=1.0)


def test_metrics_drain_bytes_per_step_reports_gbps():
    rec = telemetry.RingBufferRecorder()
    m = telemetry.init_metrics()

    @jax.jit
    def step(m):
        m = telemetry.accumulate(m)
        return telemetry.drain(m, rec, every_n=1, bytes_per_step=1e9)

    for _ in range(3):
        m = step(m)
    jax.effects_barrier()
    assert len(rec.records) == 3
    # first drain has no previous timestamp; later ones derive GB/s
    assert "achieved_gbps" not in rec.records[0]
    assert rec.records[-1]["achieved_gbps"] > 0


def test_metrics_state_donatable():
    """Every field must be its own buffer or donation breaks (the
    f(donate(a), donate(a)) XLA error)."""
    m = telemetry.init_metrics()
    step = jax.jit(lambda m: telemetry.accumulate(m, loss=1.0),
                   donate_argnums=(0,))
    m = step(m)
    m = step(m)
    assert int(m.total_steps) == 2


# ---------------------------------------------------------------------------
# LossScaler -> cumulative skip/growth counters
# ---------------------------------------------------------------------------

def test_scaler_update_scale_feeds_metrics_counters():
    from apex_tpu.amp.scaler import LossScaler

    sc = LossScaler("dynamic", init_scale=4.0, scale_window=2,
                    hysteresis=1)
    st = sc.init_state()
    m = telemetry.init_metrics()

    # overflow step: counts a skip, scale backs off 4 -> 2
    st = st._replace(found_inf=jnp.asarray(True))
    st, m = sc.update_scale(st, m)
    assert int(m.overflow_skips) == 1 and int(m.scale_growths) == 0
    assert float(m.loss_scale) == pytest.approx(2.0)

    # two clean steps: scale grows 2 -> 4 at the window
    st, m = sc.update_scale(st, m)
    st, m = sc.update_scale(st, m)
    assert int(m.overflow_skips) == 1
    assert int(m.scale_growths) == 1
    assert float(m.loss_scale) == pytest.approx(4.0)

    # metrics=None keeps the original single-return API
    st2 = sc.update_scale(st)
    assert isinstance(st2, type(st))


def test_scaler_metrics_inside_jit():
    from apex_tpu.amp.scaler import LossScaler

    sc = LossScaler("dynamic", init_scale=8.0, scale_window=1000)

    @jax.jit
    def step(st, m, found):
        st = st._replace(found_inf=found)
        st, m = sc.update_scale(st, m)
        return st, m

    st, m = sc.init_state(), telemetry.init_metrics()
    st, m = step(st, m, jnp.asarray(True))
    st, m = step(st, m, jnp.asarray(True))
    st, m = step(st, m, jnp.asarray(False))
    assert int(m.overflow_skips) == 2


# ---------------------------------------------------------------------------
# recorders
# ---------------------------------------------------------------------------

def test_jsonl_recorder_roundtrip(tmp_path):
    path = tmp_path / "t.jsonl"
    with telemetry.JsonlRecorder(path) as rec:
        rec.record({"event": "metrics", "step": 1,
                    "loss": jnp.float32(2.5)})
        rec.add_scalar("step-time", 0.125, 7)
    out = telemetry.read_jsonl(path)
    assert len(out) == 2
    assert out[0]["loss"] == pytest.approx(2.5)  # numpy scalar jsonable
    assert all("t_wall" in r for r in out)
    assert out[1] == {**out[1], "event": "scalar", "name": "step-time",
                      "value": 0.125, "step": 7}


def test_jsonl_recorder_nonfinite_values_stay_parseable(tmp_path):
    path = tmp_path / "nan.jsonl"
    with telemetry.JsonlRecorder(path) as rec:
        rec.record({"loss": float("nan"), "scale": float("inf")})
    (r,) = telemetry.read_jsonl(path)
    assert r["loss"] == "nan" and r["scale"] == "inf"
    json.dumps(r)  # strict-json parseable


def test_jsonl_recorder_rank_gating(tmp_path):
    # this process is rank 0 of 1: an explicit other-rank gate must drop
    path = tmp_path / "other_rank.jsonl"
    rec = telemetry.JsonlRecorder(path, log_rank=3)
    rec.record({"x": 1})
    rec.close()
    assert not path.exists()
    assert telemetry.is_logging_process() is True
    assert telemetry.is_logging_process(3) is False


def test_multi_and_ring_recorder():
    ring = telemetry.RingBufferRecorder(capacity=2)
    multi = telemetry.MultiRecorder(ring, telemetry.NullRecorder())
    for i in range(4):
        multi.record({"i": i})
    assert [r["i"] for r in ring.records] == [2, 3]  # ring capacity


def test_timers_sink_and_log_rank():
    from apex_tpu.transformer.pipeline_parallel._timers import Timers

    ring = telemetry.RingBufferRecorder()
    timers = Timers(sink=ring)
    timers("io").start()
    timers("io").stop()
    out = timers.log(["io"], reset=False, iteration=11)
    assert "io" in out
    assert ring.records[-1]["event"] == "timers"
    assert ring.records[-1]["iteration"] == 11
    assert "io" in ring.records[-1]["ms"]
    # Timers.write duck-types onto recorders via add_scalar
    timers.write(["io"], ring, 12)
    assert ring.records[-1]["event"] == "scalar"
    assert ring.records[-1]["name"] == "io-time"
    # an explicit non-resident log rank suppresses printing but still
    # returns the formatted line (and still records to the sink)
    t2 = Timers(log_rank=5, sink=ring)
    t2("x").start(); t2("x").stop()
    assert "x" in t2.log(["x"])


# ---------------------------------------------------------------------------
# pipeline bubble accounting
# ---------------------------------------------------------------------------

def test_bubble_fraction_textbook_formula():
    # the scan schedule IS the textbook fraction (p-1)/(m+p-1)
    for pp, m in [(2, 4), (4, 8), (4, 16), (8, 64)]:
        assert telemetry.analytic_bubble_fraction(pp, m) == pytest.approx(
            (pp - 1) / (m + pp - 1))
    # interleaving shrinks the fraction (same pp, same microbatches)
    assert (telemetry.analytic_bubble_fraction(4, 8, 2)
            < telemetry.analytic_bubble_fraction(4, 8, 1))
    # pp=1: no bubble anywhere
    assert telemetry.analytic_bubble_fraction(1, 4) == 0.0
    assert telemetry.analytic_bubble_fraction(1, 4, 1, "1f1b") == 0.0


def test_bubble_fraction_1f1b_matches_module_docs():
    # fwd_bwd_1f1b: T = n*vpp + D + pp-1, D = (vpp-1)*pp + (pp-1);
    # wasted half-ticks sum to (D + pp - 1)/T
    for pp, n, vpp in [(4, 8, 1), (4, 8, 2), (8, 16, 2)]:
        d = (vpp - 1) * pp + (pp - 1)
        t = n * vpp + d + (pp - 1)
        assert telemetry.analytic_bubble_fraction(
            pp, n, vpp, "1f1b") == pytest.approx((d + pp - 1) / t)
        assert telemetry.schedule_ticks(pp, n, vpp, "1f1b") == t


def test_tick_phases_counts_consistent():
    pp, n, vpp = 4, 8, 2
    phases = telemetry.tick_phases(pp, n, vpp, "1f1b")
    total = telemetry.schedule_ticks(pp, n, vpp, "1f1b")
    assert len(phases) == pp
    for r, row in enumerate(phases):
        assert len(row) == total
        # every rank forwards and backwards exactly n*vpp stream items
        f = sum(p in ("warmup", "steady") for p in row)
        b = sum(p in ("cooldown", "steady") for p in row)
        assert f == n * vpp and b == n * vpp
        # idle ticks grow with rank for this schedule: 2r
        assert sum(p == "idle" for p in row) == 2 * r
    # scan schedule: active ticks are steady, pp-1 idle on every rank
    for row in telemetry.tick_phases(pp, n, 1, "scan"):
        assert sum(p == "idle" for p in row) == pp - 1
        assert sum(p == "steady" for p in row) == n


def test_bubble_report_prices_the_bubble():
    rep = telemetry.bubble_report(4, 8, 1, "scan", tick_time_s=1e-3)
    assert rep["total_ticks"] == 11
    assert rep["analytic_bubble_fraction"] == pytest.approx(3 / 11)
    assert rep["reference_bubble_fraction"] == pytest.approx(3 / 11)
    assert rep["step_ms"] == pytest.approx(11.0)
    assert rep["bubble_ms_per_step"] == pytest.approx(3.0)
    with pytest.raises(ValueError):
        telemetry.bubble_report(4, 8, 1, "nope")


def test_tick_timeline_report_classifies_phases():
    tl = telemetry.TickTimeline()
    # rank 0 of a pp=2, n=2 1f1b run: F ticks 0..1, B ticks 1+?; feed a
    # hand-built sequence instead of deriving one
    seq = [(0, True, False), (1, True, True), (2, True, True),
           (3, False, True), (4, False, False)]
    for t, af, ab in seq:
        tl.hook(t, 0, af, ab)
    rep = tl.report("1f1b")
    (rank0,) = rep["per_rank"]
    assert rank0["ticks"] == {"warmup": 1, "steady": 2, "cooldown": 1,
                              "idle": 1}
    # tick-count accounting: (idle + 0.5*(warmup+cooldown)) / total
    assert rep["measured_bubble_fraction_ticks"] == pytest.approx(
        (1 + 0.5 * 2) / 5)
    # scan relabels its active (F-only) ticks as steady
    tl2 = telemetry.TickTimeline()
    tl2.hook(0, 1, False, False)
    tl2.hook(1, 1, True, False)
    rep2 = tl2.report("scan")
    assert rep2["per_rank"][0]["ticks"] == {"idle": 1, "steady": 1}


def test_emit_tick_fires_from_jitted_scan():
    from apex_tpu.transformer.pipeline_parallel.schedules.common import (
        emit_tick,
    )

    tl = telemetry.TickTimeline()

    @jax.jit
    def run():
        def body(c, t):
            emit_tick(tl, t, jnp.int32(0), t < 4, t >= 2)
            return c, None
        c, _ = jax.lax.scan(body, 0.0, jnp.arange(6))
        return c

    run()
    jax.effects_barrier()
    rep = tl.report("1f1b")
    assert rep["n_events"] == 6
    assert rep["per_rank"][0]["ticks"] == {"warmup": 2, "steady": 2,
                                           "cooldown": 2}
    # timing is attached from the second event on
    assert sum(rep["per_rank"][0]["phase_seconds"].values()) >= 0


def test_no_pipelining_microbatch_hook_forward_only():
    from apex_tpu.transformer.pipeline_parallel.schedules import (
        forward_backward_no_pipelining,
    )

    tl = telemetry.TickTimeline()
    params = {"w": jnp.eye(4)}
    mbs = jnp.ones((3, 2, 4))
    loss, grads = forward_backward_no_pipelining(
        lambda p, x: x @ p["w"], lambda y, e: jnp.mean(y ** 2),
        params, mbs, forward_only=True, microbatch_hook=tl,
    )
    jax.effects_barrier()
    assert grads is None
    assert tl.report("scan")["n_events"] == 3
    # numerics are identical with the hook attached
    loss_bare, _ = forward_backward_no_pipelining(
        lambda p, x: x @ p["w"], lambda y, e: jnp.mean(y ** 2),
        params, mbs, forward_only=True,
    )
    assert float(loss) == pytest.approx(float(loss_bare))


def test_no_pipelining_hook_fires_on_gradient_path():
    """This schedule's scan is never differentiated THROUGH (grad runs
    inside the body), so the hook must fire on the gradient path too —
    with unchanged gradients."""
    from apex_tpu.transformer.pipeline_parallel.schedules import (
        forward_backward_no_pipelining,
    )

    tl = telemetry.TickTimeline()
    params = {"w": jnp.eye(4)}
    mbs = jnp.ones((2, 2, 4))
    loss, grads = forward_backward_no_pipelining(
        lambda p, x: x @ p["w"], lambda y, e: jnp.mean(y ** 2),
        params, mbs, microbatch_hook=tl,
    )
    jax.effects_barrier()
    assert tl.report("1f1b")["n_events"] == 2
    # backward-active flag rides the emission on the grad path
    assert all(ev["active_b"] for ev in tl.events)
    _, grads_bare = forward_backward_no_pipelining(
        lambda p, x: x @ p["w"], lambda y, e: jnp.mean(y ** 2),
        params, mbs,
    )
    assert jnp.allclose(grads["w"], grads_bare["w"])


def test_1f1b_tick_hook_timeline_matches_analytic():
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu.transformer.pipeline_parallel.schedules.fwd_bwd_1f1b \
        import pipeline_forward_backward_1f1b

    pp, n = 4, 8
    mesh = Mesh(np.array(jax.devices()[:pp]), ("pipeline",))
    tl = telemetry.TickTimeline()
    params = {"w": jnp.zeros((pp, 8, 8))}
    inputs = jnp.zeros((n, 2, 8))
    targets = jnp.zeros((n, 2, 8))

    def local(p, i, t):
        p = jax.tree_util.tree_map(lambda q: q[0], p)
        loss, _, _ = pipeline_forward_backward_1f1b(
            lambda pc, x: jnp.tanh(x @ pc["w"]),
            lambda y, e: jnp.mean((y - e) ** 2),
            p, i, t, axis_name="pipeline", tick_hook=tl)
        return loss

    f = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("pipeline"), P(), P()),
        out_specs=P(), check_vma=False))
    f(params, inputs, targets)
    jax.effects_barrier()

    total = telemetry.schedule_ticks(pp, n, 1, "1f1b")
    rep = tl.report("1f1b")
    assert rep["n_events"] == pp * total
    # measured tick-count fraction equals the analytic fraction exactly
    # (every tick executes; phases are derived from the same flags)
    assert rep["measured_bubble_fraction_ticks"] == pytest.approx(
        telemetry.analytic_bubble_fraction(pp, n, 1, "1f1b"))
    # phase counts agree with the analytic per-rank timeline
    analytic = telemetry.tick_phases(pp, n, 1, "1f1b")
    for rank_rep in rep["per_rank"]:
        r = rank_rep["rank"]
        want = {}
        for ph in analytic[r]:
            want[ph] = want.get(ph, 0) + 1
        assert rank_rep["ticks"] == want


# ---------------------------------------------------------------------------
# tracing: fixture-parsed xplane events + cost-analysis fallback
# ---------------------------------------------------------------------------

def test_aggregate_op_times_fixture():
    events = [
        ("%convolution_tanh_fusion.3 = bf16[4,4] fusion(...)", 100),
        ("%convolution_tanh_fusion.9 = bf16[4,4] fusion(...)", 50),
        ("%while.7 = (s32[], f32[8]) while(...)", 1000),  # container
        ("%conditional.2 = f32[] conditional(...)", 500),  # container
        ("%apex_tpu_flash_fwd.65 = (bf16[8]) custom-call(...)", 200),
        ("%copy-done", 25),
    ]
    total, per_op = telemetry.aggregate_op_times(events)
    assert total == 375  # containers excluded, suffixes merged
    assert per_op == {
        ("convolution_tanh_fusion", "matmul/conv"): 150,
        ("apex_tpu_flash_fwd", "attention-kernel"): 200,
        ("copy-done", "data-movement"): 25,
    }


def test_aggregate_generic_fusions_split_by_hlo_category():
    """The round-5 misattribution: every generic %fusion.N merged into
    one 'fusion' op booked as elementwise, hiding the dense GEMMs. With
    the profiler's hlo_category stat they stay separate."""
    events = [
        ("%fusion.1 = bf16[4,4] fusion(...)", 700, "convolution fusion"),
        ("%fusion.2 = f32[4] fusion(...)", 200, "loop fusion"),
        ("%fusion.3 = f32[4] fusion(...)", 100, None),  # no stat
    ]
    total, per_op = telemetry.aggregate_op_times(events)
    assert total == 1000
    assert per_op == {
        ("fusion", "matmul/conv"): 700,
        ("fusion", "fusion(elementwise)"): 200,
        ("fusion", "fusion(unattributed)"): 100,
    }


def test_breakdown_table_fixture():
    total, per_op = telemetry.aggregate_op_times([
        ("%dot_fusion.1 = ...", 3_000_000),
        ("%all-reduce.2 = ...", 1_000_000),
    ])
    table = telemetry.breakdown_table(total, per_op, n_steps=2, top=1)
    assert table["source"] == "xplane"
    assert table["device_ms_per_step"] == pytest.approx(0.002)
    assert len(table["ops"]) == 1  # top=1
    assert table["ops"][0]["op"] == "dot_fusion"
    assert table["ops"][0]["category"] == "matmul/conv"
    assert table["ops"][0]["pct"] == pytest.approx(75.0)
    assert table["categories"]["collective"]["pct"] == pytest.approx(25.0)
    assert telemetry.breakdown_table(0, {}) is None


# --- device time by layer scope (telemetry.tracing.LAYER_SCOPES) ----------
_STEP_HLO = """
HloModule jit_step

%fused_computation.2 (p: f32[8,8]) -> f32[8,8] {
  %p = f32[8,8]{1,0} parameter(0)
  ROOT %tanh.5 = f32[8,8]{1,0} tanh(%p), metadata={op_name="jit(step)/jvp()/apex_tpu.layer_stack/checkpoint/apex_tpu.transformer_layer/apex_tpu.mlp/tanh" source_file="x.py" source_line=3}
}

ENTRY %main.3 (x: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[8,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.2
  %fusion.2 = f32[8,8]{1,0} fusion(%fusion.1), kind=kLoop, calls=%fc.9, metadata={op_name="jit(step)/transpose(jvp())/apex_tpu.layer_stack/checkpoint/rematted_computation/apex_tpu.transformer_layer/apex_tpu.attention/apex_tpu.flash_attention/mul"}
  %custom-call.3 = f32[8,8]{1,0} custom-call(%fusion.2), metadata={op_name="jit(step)/apex_tpu.optimizer_step/cond/branch_1_fun/apex_tpu.packed_adam/apex_tpu_packed_adam"}
  ROOT %add.4 = f32[8,8]{1,0} add(%custom-call.3, %x), metadata={op_name="jit(step)/add"}
}
"""


@pytest.mark.parametrize("path, want", [
    ("jit(s)/jvp()/closed_call/apex_tpu.transformer_layer/apex_tpu.mlp/mul",
     ("apex_tpu.mlp", "fwd")),
    ("jit(s)/transpose(jvp(apex_tpu.loss))/jvp(apex_tpu.loss)/checkpoint/"
     "rematted_computation/apex_tpu.transformer_layer/apex_tpu.mlp/mul",
     ("apex_tpu.mlp", "recompute")),
    ("jit(s)/transpose(jvp())/apex_tpu.layer_stack/checkpoint/"
     "apex_tpu.transformer_layer/apex_tpu.fused_block/add",
     ("apex_tpu.transformer_layer", "bwd")),
    ("jit(s)/transpose(jvp(apex_tpu.layer_stack))/concatenate",
     ("apex_tpu.layer_stack", "bwd")),
    ("jit(s)/jvp(apex_tpu.embed)/jit(_take)/gather",
     ("apex_tpu.embed", "fwd")),
    ("jit(s)/apex_tpu.optimizer_step/cond/apex_tpu.unpack/slice",
     ("apex_tpu.optimizer_step", "fwd")),
    ("jit(s)/apex_tpu.sync_gradients/apex_tpu.grad_bucket/3/psum",
     ("apex_tpu.sync_gradients", "fwd")),
    ("jit(s)/apex_tpu.flash_attention/apex_tpu_flash_fwd", (None, "fwd")),
    ("", (None, "fwd")),
])
def test_scope_of_reads_layer_and_phase_through_jax_wrappers(path, want):
    assert telemetry.scope_of(path) == want


def test_layer_scopes_are_named_once_and_containers_are_among_them():
    from apex_tpu.telemetry import tracing

    assert len(set(telemetry.LAYER_SCOPES)) == len(telemetry.LAYER_SCOPES)
    assert set(tracing.CONTAINER_SCOPES) < set(telemetry.LAYER_SCOPES)
    assert all(s.startswith("apex_tpu.") for s in telemetry.LAYER_SCOPES)


def test_scope_index_maps_instructions_and_a_fusion_takes_its_root():
    index = telemetry.scope_index(_STEP_HLO)
    assert index["fusion.1"].endswith("apex_tpu.mlp/tanh")    # the root's
    assert index["fusion.2"].endswith("apex_tpu.flash_attention/mul")
    assert index["add.4"] == "jit(step)/add"
    assert "tanh.5" in index and "main.3" not in index


def test_breakdown_table_scopes_by_layer_and_phase():
    events = [
        ("%fusion.1 = f32[8,8] fusion(...)", 600),
        ("fusion.2", 200),
        ("custom-call.3", 100),
        ("add.4", 60),
        ("fusion.77", 40),                       # not in the text
        ("%while.9 = (...) while(...)", 5000),   # container: dropped
    ]
    total, per_op = telemetry.aggregate_op_times(events)
    per_scope = telemetry.aggregate_scope_times(
        events, telemetry.scope_index(_STEP_HLO))
    assert per_scope == {
        ("apex_tpu.mlp", "fwd"): 600,
        ("apex_tpu.attention", "recompute"): 200,
        ("apex_tpu.optimizer_step", "fwd"): 100,
        ("_unscoped_", "fwd"): 100,
    }
    assert sum(per_scope.values()) == total
    table = telemetry.breakdown_table(total, per_op, n_steps=2,
                                      per_scope=per_scope)
    scopes = table["scopes"]
    assert list(scopes)[0] == "apex_tpu.mlp"          # largest first
    assert scopes["apex_tpu.mlp"]["pct"] == pytest.approx(60.0)
    assert scopes["apex_tpu.attention"]["phases"] == {
        "recompute": {"ms_per_step": 0.0, "pct": pytest.approx(20.0)}}
    assert scopes["_unscoped_"]["pct"] == pytest.approx(10.0)
    # without the step's text the table is the one it was
    assert "scopes" not in telemetry.breakdown_table(total, per_op)


def test_profile_step_cost_analysis_fallback_on_cpu():
    @jax.jit
    def step(x):
        return (jnp.tanh(x @ x),)

    table = telemetry.profile_step(step, (jnp.ones((32, 32)),), n_steps=2)
    assert table is not None
    assert table["source"] == "cost_analysis"
    assert table["flops_per_step"] > 0
    assert table["arithmetic_intensity"] is None or \
        table["arithmetic_intensity"] > 0


def test_trace_session_parse_after_exit_only():
    with telemetry.trace_session() as sess:
        jnp.ones((4,)).block_until_ready()
        with pytest.raises(RuntimeError):
            sess.op_breakdown()
    # CPU backend: no TPU device plane -> no xplane table
    assert sess.op_breakdown() is None


def test_trace_session_usable_after_traced_block_raises():
    """The profiler stops (and writes) even when the block raises; the
    session must be parseable afterwards, not stuck 'active'."""
    with pytest.raises(ValueError, match="boom"):
        with telemetry.trace_session() as sess:
            raise ValueError("boom")
    assert sess.active is False
    assert sess.op_breakdown() is None  # no device plane on CPU


# ---------------------------------------------------------------------------
# packed-optimizer sweep bytes (the GB/s-per-drain denominator)
# ---------------------------------------------------------------------------

def test_packed_state_sweep_bytes():
    from apex_tpu.optimizers import FusedAdam, FusedSGD

    params = {"w": jnp.zeros((2048,), jnp.bfloat16)}
    adam = FusedAdam(lr=1e-3, master_weights=True, packed=True).init(params)
    # bf16 grads read + params write (2+2) + fp32 m, v, master r/w (24)
    assert adam.sweep_bytes() == 28 * adam.spec.total
    sgd = FusedSGD(lr=0.1, momentum=0.9, packed=True).init(params)
    # bf16 in/out + fp32 momentum r/w
    assert sgd.sweep_bytes() == 12 * sgd.spec.total
