"""``correct`` has to come out false when it should.

- The control: the reference put in the program's place and computed in
  float8 (the precision below the bf16 the configurations state) fails the
  limits, at a size a test run can hold (the chip's readings at the cells'
  own sizes are in PERF.md).
- The rest of a run, driven past the harness's look for a chip at the tiny
  sizes of ``rehearsal.json``, with the timed path broken underneath: a
  step that returns its state unchanged; half of the batch left out; the
  exchange between chips left out. Each has to read ``correct`` false, and
  the sound path true.
"""
import io
import json
import os
import sys
from unittest import mock

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import control, manifest as mf  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark import train_cell  # noqa: E402


def _drive(name, seed=11, seconds=1.0, **runner_kw):
    """One rehearsal run of a cell; the result line as a dict."""
    cell = mf.Cell(mf.load_manifest(), name)
    out = io.StringIO()
    # buckets small enough that the tiny model fills several, as the
    # published one does at the library's own cap
    with mock.patch.object(train_cell, "BUCKET_CAP_MB", 0.05):
        rc = harness.run_cell(cell, seed, seconds, False, rehearse=True,
                              runner_kw=runner_kw, out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def _planted(wrap):
    """A program factory that builds the real program and then re-jits its
    step through ``wrap(step_fn, n_state)``."""
    def factory(config, mix, seed, devices, interpret):
        program = train_cell.build_program(config, mix, seed, devices,
                                           interpret)
        program.rejit(wrap(program.step_fn, len(program.state)))
        return program
    return factory


def state_unchanged(step_fn, n):
    def step(*args):
        out = step_fn(*args)
        return (*args[:n], out[n])
    return step


def half_batch(step_fn, n):
    def step(*args):
        tokens, labels = args[n], args[n + 1]
        half = tokens.shape[0] // 2
        return step_fn(*args[:n], tokens[:half], labels[:half])
    return step


TRAIN = ["gpt2-345m.train-1chip", "bert-large.train-1chip",
         "gpt2-345m.train-dp4"]


@pytest.mark.parametrize("name", TRAIN)
def test_sound_training_run_is_correct(name):
    line = _drive(name)
    assert line["correct"] is True, line["compared"]
    assert line["device"]["platform"] == "cpu" and line["metrics"] == {}


@pytest.mark.parametrize("name", TRAIN[:2])
@pytest.mark.parametrize("fault", [state_unchanged, half_batch])
def test_broken_training_step_is_not_correct(name, fault):
    line = _drive(name, program_factory=_planted(fault))
    assert line["correct"] is False
    failed = [k for k, row in line["compared"].items() if not row["ok"]]
    assert failed, line["compared"]


def test_exchange_between_chips_left_out_is_not_correct():
    """Every collective sum of the data-parallel step turned into the
    local value: each replica steps on its own rows' gradient."""
    def factory(config, mix, seed, devices, interpret):
        with mock.patch.object(jax.lax, "psum", lambda x, *a, **k: x), \
                mock.patch.object(jax.lax, "pmean", lambda x, *a, **k: x):
            program = train_cell.build_program(config, mix, seed, devices,
                                               interpret)
            real = program.compile

            def compile_patched(tokens, labels):
                with mock.patch.object(jax.lax, "psum",
                                       lambda x, *a, **k: x), \
                        mock.patch.object(jax.lax, "pmean",
                                          lambda x, *a, **k: x):
                    real(tokens, labels)
            program.compile = compile_patched
        return program

    line = _drive("gpt2-345m.train-dp4", program_factory=factory)
    assert line["correct"] is False
    assert not line["compared"]["grad1_worst_leaf_gap"]["ok"]


@pytest.mark.parametrize("name", TRAIN[:2])
def test_control_and_planted_faults_fail_the_cell_s_limits(name):
    cell = mf.Cell(mf.load_manifest(), name)
    harness.rehearsal_cell(cell)
    fails = control.verdicts(cell, control.train_readings(cell, seed=21))
    assert set(fails) == {"control_float8", "half_batch", "state_unchanged"}
    assert all(fails.values()), fails      # each fails a number of the cell
    assert "change_worst_leaf_gap" in fails["state_unchanged"]


def test_nothing_compared_is_not_correct():
    assert harness.judge({"x": 1.0}, {}) == (False, {})
    ok, rows = harness.judge({}, {"max": {"x": 1.0}})
    assert not ok and rows["x"]["value"] is None
    ok, _ = harness.judge({"x": float("nan")}, {"max": {"x": 1.0}})
    assert not ok
