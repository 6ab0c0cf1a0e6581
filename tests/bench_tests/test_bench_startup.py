"""Set-up from the inside (``benchmark/startup_reduce.py``): the five
``startup.*`` numbers by hand on a canned list of spans round a planted
window instant; the readers on runs with no window to place and on a
program without a ledger; a live case through jax's own events on the
CPU (what a program counts, never a time of the chip's)."""
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import manifest as mf  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark import startup_reduce as su  # noqa: E402

M = mf.load_manifest()
FIVE = ["startup.trace_s", "startup.lower_s", "startup.cache_load_s",
        "startup.programs", "startup.largest_program_s"]

WINDOW = 100.0
SPANS = [
    # the weights' initialisation: a small program of its own
    ("trace", "<lambda>", 80.0, 80.5),
    ("lower", "<lambda>", 80.5, 80.75),
    ("compile", "<lambda>", 80.75, 81.0),
    # two more programs of that name (the weights made again for the
    # check, a small read): together over the step, none of them alone
    ("trace", "<lambda>", 82.0, 83.0),
    ("lower", "<lambda>", 83.0, 84.0),
    ("compile", "<lambda>", 84.0, 89.0),
    ("trace", "<lambda>", 89.0, 89.25),
    ("lower", "<lambda>", 89.25, 89.5),
    ("compile", "<lambda>", 89.5, 89.75),
    # the step: a kernel traced twice inside its trace
    ("trace", "_flash_fwd", 91.0, 92.0),
    ("trace", "_flash_fwd", 92.5, 93.0),
    ("trace", "train_step", 90.0, 94.0),
    ("lower", "train_step", 94.0, 95.5),
    ("compile", "train_step", 95.5, 98.5),
    # a warm-up read that straddles the instant: starts before it, whole
    ("compile", "read_change", 99.5, 100.5),
    # the reference, half a minute later
    ("trace", "reference_steps", 130.0, 131.0),
    ("lower", "reference_steps", 131.0, 131.5),
    ("compile", "reference_steps", 131.5, 135.5),
]
CACHE = [
    (80.75, "requests", 1), (81.0, "hits", 1), (81.0, "retrieval_s", 0.25),
    (84.0, "requests", 1), (89.0, "misses", 1),
    (95.5, "requests", 1), (98.5, "hits", 1), (98.5, "retrieval_s", 2.75),
    (99.875, "retrieval_s", 0.375),
    (131.5, "requests", 1), (135.5, "hits", 1), (135.5, "retrieval_s", 3.5),
]


def _run(**phases):
    return {"cell": "x", "platform": "cpu", "phases": {
        "entry.compile_s": 1.0, "entry.cache_misses": 0,
        "entry.build_s": 1.0, "entry.warm_s": 1.0, **phases},
        "counters": {}, "end_to_end": {}, "trace": None, "peaks": None,
        "notes": {}}


def test_the_five_numbers_of_a_canned_list_by_hand():
    t = su.reduce(SPANS, CACHE, WINDOW)
    assert t["startup.trace_s"] == 1.75 + 4.0       # the kernel's inside
    assert t["startup.lower_s"] == 1.5 + 1.5
    assert t["startup.cache_load_s"] == 0.25 + 2.75 + 0.375
    assert t["startup.programs"] == 5.0
    # a program is one executable: the three <lambda>s come to 8.75 s
    # together and are 1.0, 7.0 and 0.75
    assert t["largest_program"] == "train_step"
    assert t["startup.largest_program_s"] == 4.0 + 1.5 + 3.0
    assert sorted(su.programs([s for s in SPANS if s[2] < WINDOW])) == [
        (0.75, "<lambda>"), (1.0, "<lambda>"), (1.0, "read_change"),
        (7.0, "<lambda>"), (8.5, "train_step")]
    assert t["compile_s"] == 5.5 + 3.0 + 1.0
    assert t["covered_s"] == 1.0 + 7.75 + 8.5 + 1.0
    assert t["cache"] == {"requests": 3, "hits": 2, "misses": 1}
    # by function, self seconds: the step's trace less the kernel's two
    rows = {(k, n): (c, s) for k, n, c, s in t["top"]}
    assert rows[("trace", "train_step")] == (1, 2.5)
    assert rows[("trace", "_flash_fwd")] == (2, 1.5)
    assert rows[("compile", "train_step")] == (1, 3.0)
    assert not any(n == "reference_steps" for _, n in rows)
    assert rows[("compile", "<lambda>")] == (3, 5.5)
    assert t["top"][0][:2] == ["compile", "<lambda>"]
    assert len(t["top"]) <= su.TOP


def test_a_window_before_everything_holds_nothing():
    t = su.reduce(SPANS, CACHE, 50.0)
    assert [t[name] for name in FIVE] == [0.0, 0.0, 0.0, 0.0, 0.0]
    assert t["largest_program"] is None and t["top"] == []


def test_a_program_takes_the_spans_of_its_name_since_the_last_compile():
    spans = [("trace", "f", 0.0, 2.0), ("trace", "f", 0.5, 1.0),  # nested
             ("trace", "g", 1.0, 1.5),                 # never compiled
             ("lower", "f", 2.0, 3.0), ("compile", "f", 3.0, 4.0),
             ("compile", "f", 6.0, 6.5),               # loaded, no trace
             ("trace", "f", 7.0, 7.25), ("compile", "f", 8.0, 9.0)]
    assert su.programs(spans) == [(4.0, "f"), (0.5, "f"), (1.25, "f")]
    assert su.programs([]) == []


@pytest.mark.parametrize("metric", FIVE)
def test_a_reader_without_a_window_to_place_says_nothing(metric):
    assert mf.reader(metric)(_run()) is None


@pytest.mark.parametrize("metric", FIVE)
def test_a_reader_of_a_program_without_the_ledger_says_nothing(
        metric, monkeypatch):
    """What the parent commit is to this benchmark: no module to read."""
    monkeypatch.setitem(sys.modules, "apex_tpu.telemetry.compiles", None)
    run = _run(process_to_window_s=harness._process_age_s())
    run["cell"] = "no-ledger-" + metric
    assert mf.reader(metric)(run) is None


def test_a_live_jit_is_read_through_the_ledger_and_a_placed_window(capsys):
    """A window placed now holds what was jitted before it and not what
    is jitted after; the table is printed once."""
    from apex_tpu.telemetry import compiles

    def toy(x):
        time.sleep(0.02)                 # runs while jax traces: > floor
        for _ in range(200):
            x = jnp.tanh(x) @ x + 1.0
        return x.sum()

    jnp.ones(3).block_until_ready()      # a ledger that has heard something
    before = _run(process_to_window_s=harness._process_age_s())
    before["cell"] = "live-before"
    t0 = su.table_of(before)
    since = time.perf_counter()
    jax.jit(toy)(jnp.ones((8, 8))).block_until_ready()
    mine = {s[0] for s in compiles.spans()
            if s[1] == "toy" and s[2] >= since}
    assert mine == {"trace", "lower", "compile"}
    after = _run(process_to_window_s=harness._process_age_s())
    after["cell"] = "live-after"
    values = {name: mf.reader(name)(after) for name in FIVE}
    assert values["startup.trace_s"] >= t0["startup.trace_s"] + 0.02
    assert values["startup.lower_s"] > t0["startup.lower_s"]
    assert values["startup.programs"] >= t0["startup.programs"] + 1
    assert values["startup.programs"] >= 1
    assert values["startup.largest_program_s"] > 0
    assert values["startup.cache_load_s"] >= 0
    # the window placed before the jit still reads what it read
    assert su.value(before, "startup.programs") == t0["startup.programs"]
    said = [line for line in capsys.readouterr().err.splitlines()
            if line.startswith("[bench cpu] startup ")]
    assert len(said) == 2 and '"uncovered_s"' in said[1]


def test_the_window_instant_is_on_perf_counters_clock():
    now = time.perf_counter()
    placed = su.window_instant(harness._process_age_s())
    assert abs(placed - now) < 0.05          # /proc's tick is 10 ms


def test_the_manifest_holds_the_five_metrics_and_is_sound():
    assert mf.check(M) == []
    rows = {m["name"]: m for m in M["per_layer"]}
    # appended, and in this order among themselves; a later PR's metrics
    # come after them
    assert [n for n in rows if n.startswith("startup.")] == FIVE
    for name in FIVE:
        m = rows[name]
        assert (m["layer"], m["moves"], m["better"]) == (
            "entry points", "setup_s", "lower")
        assert "workloads" not in m
        assert callable(mf.reader(name))
    assert rows["startup.programs"]["unit"] == "count"
    # every cell reports setup_s, so every cell reports the five
    for w in M["workloads"]:
        cell = mf.Cell(M, w["name"])
        assert set(FIVE) <= {m["name"] for m in cell.per_layer}
