"""The compile ledger (``apex_tpu/telemetry/compiles.py``): jax's trace,
lower and compile-or-load spans by function, on ``perf_counter``'s clock.

The live cases register a ledger of their own with no floor beside the
package's (a toy function traces in under the package's millisecond), and
sleep in the functions' Python bodies, which run only while jax traces
them, so that a trace span has a length a test can hold it to.
"""
import contextlib
import importlib
import logging
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring as jax_monitoring

from apex_tpu.telemetry import compiles

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
INNER_S, OUTER_S = 0.03, 0.01


def _listeners():
    return (len(jax_monitoring.get_event_time_span_listeners()),
            len(jax_monitoring.get_event_listeners()),
            len(jax_monitoring.get_event_duration_listeners()))


@contextlib.contextmanager
def listening(led):
    """``led`` beside the package's ledger, for one test."""
    jax.monitoring.register_event_time_span_listener(led.on_span)
    jax.monitoring.register_event_listener(led.on_event)
    jax.monitoring.register_event_duration_secs_listener(led.on_seconds)
    try:
        yield led
    finally:
        jax.monitoring.unregister_event_time_span_listener(led.on_span)
        jax.monitoring.unregister_event_listener(led.on_event)
        jax.monitoring.unregister_event_duration_listener(led.on_seconds)


@pytest.fixture
def ledger():
    """A ledger without a floor, listening."""
    with listening(compiles.CompileLedger(floor_s=0.0)) as led:
        yield led


def _toy_step():
    """A step whose jitted inner function is called three times and once
    under ``jax.checkpoint``; fresh functions, so nothing is cached."""
    @jax.jit
    def toy_inner(x):
        time.sleep(INNER_S)
        return jnp.tanh(x) @ x

    def toy_step(x):
        time.sleep(OUTER_S)
        for _ in range(3):
            x = toy_inner(x)
        return jax.checkpoint(toy_inner)(x).sum()

    return jax.jit(jax.grad(toy_step))


def test_a_toy_step_gives_spans_of_all_three_kinds_by_function(ledger):
    _toy_step()(jnp.ones((16, 16))).block_until_ready()
    names = {(s.kind, s.fun_name) for s in ledger.spans()}
    assert {("trace", "toy_inner"), ("trace", "toy_step"),
            ("lower", "toy_step"), ("compile", "toy_step")} <= names
    for s in ledger.spans():
        assert s.end >= s.start
    table = ledger.by_function()
    assert table[("compile", "toy_step")]["count"] == 1
    assert table[("trace", "toy_inner")]["total_s"] >= INNER_S


def test_self_seconds_of_the_outer_trace_leave_out_the_inner(ledger):
    _toy_step()(jnp.ones((16, 16))).block_until_ready()
    table = ledger.by_function()
    outer, inner = table[("trace", "toy_step")], table[("trace", "toy_inner")]
    assert outer["total_s"] >= OUTER_S + inner["total_s"]
    assert outer["self_s"] <= outer["total_s"] - inner["total_s"] + 1e-9
    assert outer["self_s"] >= OUTER_S
    assert inner["self_s"] == pytest.approx(inner["total_s"], abs=5e-3)


def test_totals_are_the_union_and_nested_spans_are_not_doubled(ledger):
    t0 = time.perf_counter()
    _toy_step()(jnp.ones((16, 16))).block_until_ready()
    wall = time.perf_counter() - t0
    table, totals = ledger.by_function(), ledger.totals()
    by_sum = sum(row["total_s"] for (kind, _), row in table.items()
                 if kind == "trace")
    outer = table[("trace", "toy_step")]["total_s"]
    assert totals["trace_s"] == pytest.approx(outer, abs=5e-3)
    assert totals["trace_s"] <= by_sum - INNER_S
    # the three kinds of one thread do not overlap, and lie inside the call
    assert totals["covered_s"] == pytest.approx(
        totals["trace_s"] + totals["lower_s"] + totals["compile_s"])
    assert totals["covered_s"] <= wall
    assert totals["programs"] == sum(
        row["count"] for (kind, _), row in table.items()
        if kind == "compile")


def test_the_package_installs_the_ledger_once():
    before = _listeners()
    assert compiles.LEDGER.on_span in (
        jax_monitoring.get_event_time_span_listeners())
    compiles.install()
    importlib.import_module("apex_tpu.telemetry")
    importlib.reload(sys.modules["apex_tpu.telemetry"])
    assert _listeners() == before
    assert jax_monitoring.get_event_time_span_listeners().count(
        compiles.LEDGER.on_span) == 1


def test_chip_smoke_registers_no_listener_of_its_own():
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import chip_smoke

    before = _listeners()
    with open(os.devnull, "w") as out:
        smoke = chip_smoke.Smoke(out=out)
        with smoke.section("one") as rec:
            jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
        assert _listeners() == before
        smoke.close()
    assert rec["compile_s"] > 0 and rec["cache_hits"] == 0
    assert rec["compile_s"] <= rec["wall_s"] + 1e-3


def test_events_under_the_floor_are_counted_and_keep_no_span():
    led = compiles.CompileLedger()
    for _ in range(1000):
        led.on_span(TRACE_EVENT, 10.0, 10.0 + 2e-5, fun_name="add")
    led.on_span(LOWER_EVENT, 10.0, 10.0002, fun_name="jit(add)")
    assert led.spans() == [] and led.by_function() == {}
    short = led.totals()["under_floor"]
    assert short["trace"][0] == 1000 and short["lower"][0] == 1
    assert short["trace"][1] == pytest.approx(0.02)
    # an executable is kept however quick its load
    led.on_span(COMPILE_EVENT, 10.0, 10.0002, fun_name="jit(add)")
    assert [(s.kind, s.fun_name) for s in led.spans()] == [
        ("compile", "add")]
    led.on_span("/jax/some/other/span", 0.0, 5.0, fun_name="x")
    assert len(led.spans()) == 1


def test_past_the_cap_spans_fold_into_sums_and_lose_no_seconds():
    led = compiles.CompileLedger(cap=4)
    for i in range(10):
        led.on_span(TRACE_EVENT, float(i), i + 0.25, fun_name="_flash_fwd")
        led.on_span(COMPILE_EVENT, i + 0.5, i + 0.75, fun_name="jit(k)")
    assert len(led.spans()) == 4
    table, totals = led.by_function(), led.totals()
    assert table[("trace", "_flash_fwd")] == {
        "count": 10, "total_s": 2.5, "self_s": 2.5}
    assert table[("compile", "k")]["count"] == 10
    assert totals["trace_s"] == pytest.approx(2.5)
    assert totals["compile_s"] == pytest.approx(2.5)
    assert totals["programs"] == 10 and totals["folded"] == 16
    for _ in range(6):
        led.on_seconds(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
    assert len(led.cache_events()) == 4
    assert led.totals()["retrieval_s"] == 3.0


def test_spans_are_on_perf_counters_clock(ledger):
    f = jax.jit(lambda x: jnp.sin(x) + 1)
    t0 = time.perf_counter()
    f.lower(jnp.ones(8)).compile()
    t1 = time.perf_counter()
    last = ledger.spans()[-1]
    assert last.kind == "compile"
    assert t0 <= last.start <= last.end <= t1 + 5e-3
    assert t1 - last.end < 0.1
    assert all(t0 <= s.start <= s.end <= t1 + 5e-3 for s in ledger.spans())


def test_after_steady_a_new_shape_is_counted_and_logged_once(caplog):
    """At the package's own floor: the arrays are made before
    ``steady()``, so that what comes after is the function's alone."""
    @jax.jit
    def toy_loss(x):
        time.sleep(OUTER_S)
        return (x * x).sum()

    a, b, c = jnp.ones(8), jnp.ones(9), jnp.ones(10)
    compiles.logger.addHandler(caplog.handler)    # the package's logger
    try:                                          # does not propagate
        with listening(compiles.CompileLedger()) as led:
            toy_loss(a).block_until_ready()
            led.steady()
            toy_loss(a).block_until_ready()
            assert led.recompiles_after_steady == 0 and not caplog.records
            toy_loss(b).block_until_ready()
            first = led.recompiles_after_steady
            assert first >= 1
            toy_loss(c).block_until_ready()
    finally:
        compiles.logger.removeHandler(caplog.handler)
    assert led.recompiles_after_steady == 2 * first
    assert led.totals()["recompiles_after_steady"] == 2 * first
    said = [r.getMessage() for r in caplog.records]
    assert len(said) == 1 and "toy_loss" in said[0] and " s " in said[0]
    assert caplog.records[0].levelno == logging.WARNING


def test_a_compiled_step_emits_nothing_in_steady_state(ledger):
    step = _toy_step()
    x = jnp.ones((16, 16))
    step(x).block_until_ready()
    calls = []
    listener = lambda event, *a, **kw: calls.append(event)
    jax.monitoring.register_event_time_span_listener(listener)
    try:
        n, short = len(ledger.spans()), ledger.totals()["under_floor"]
        mine = len(compiles.spans())
        for _ in range(10):
            x = step(x)
        x.block_until_ready()
    finally:
        jax.monitoring.unregister_event_time_span_listener(listener)
    assert calls == []
    assert len(ledger.spans()) == n and len(compiles.spans()) == mine
    assert ledger.totals()["under_floor"] == short


def test_the_cache_counters_keep_their_instants():
    led = compiles.CompileLedger()
    led.on_event("/jax/compilation_cache/compile_requests_use_cache")
    led.on_event("/jax/compilation_cache/cache_hits")
    led.on_seconds("/jax/compilation_cache/cache_retrieval_time_sec", 1.5)
    led.on_seconds("/jax/compilation_cache/compile_time_saved_sec", 40.0)
    led.on_seconds(COMPILE_EVENT, 2.0)           # a span's, not a counter
    cut = time.perf_counter()
    led.on_event("/jax/compilation_cache/compile_requests_use_cache")
    led.on_event("/jax/compilation_cache/cache_misses")
    events = led.cache_events()                  # what nothing reads is
    assert [name for _, name, _ in events] == [  # not kept: time saved
        "requests", "hits", "retrieval_s", "requests", "misses"]
    assert [at < cut for at, _, _ in events] == [True] * 3 + [False] * 2
    whole = led.totals()
    assert (whole["requests"], whole["hits"], whole["misses"]) == (2, 1, 1)
    assert whole["retrieval_s"] == 1.5 and "saved_s" not in whole


def test_a_straddling_and_a_late_span_keep_their_places():
    """What the benchmark's reduction cuts at the window's instant."""
    led = compiles.CompileLedger()
    to_wall = time.time() - time.perf_counter()
    now = time.perf_counter()
    for kind, name, a, b in (
            (TRACE_EVENT, "step", -10.0, -6.0),
            (TRACE_EVENT, "_flash_fwd", -9.0, -8.0),
            (COMPILE_EVENT, "jit(step)", -5.0, -2.0),
            (COMPILE_EVENT, "jit(reference)", 30.0, 34.0)):
        led.on_span(kind, now + to_wall + a, now + to_wall + b,
                    fun_name=name)
    assert [(s.fun_name, s.start < now) for s in led.spans()] == [
        ("step", True), ("_flash_fwd", True), ("step", True),
        ("reference", False)]
    for s, (a, b) in zip(led.spans(), ((-10, -6), (-9, -8), (-5, -2),
                                       (30, 34))):
        assert s.start - now == pytest.approx(a, abs=1e-3)
        assert s.end - now == pytest.approx(b, abs=1e-3)
    totals = led.totals()
    assert totals["trace_s"] == pytest.approx(4.0, abs=1e-3)
    assert totals["compile_s"] == pytest.approx(7.0, abs=1e-3)
    assert totals["programs"] == 2
    assert led.by_function()[("trace", "step")]["self_s"] == (
        pytest.approx(3.0, abs=1e-3))
