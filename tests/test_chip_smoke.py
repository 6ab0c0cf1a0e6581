"""``chip_smoke.py`` and the rules measurement entry points share.

- off-chip the smoke exits non-zero before compiling anything, naming
  the backend; alone in a directory it fails too;
- every leg passes at toy width on the CPU mesh with interpreted kernels;
- the kernel-presence assertion goes red when a gate turns a kernel off,
  and the dense-reference check goes red on a wrong token;
- the compile-cache rule (``apex_tpu.chip.use_compile_cache``).
"""
import dataclasses
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from apex_tpu import chip  # noqa: E402

TOY = chip_smoke.Size(
    layers=2, hidden=128, heads=4, vocab=512, seq=64, batch=2,
    train_steps=3, n_slots=4, n_requests=4, prompt_len=16, new_tokens=8,
    bucket_cap_mb=0.25, interpret=True)


@pytest.fixture
def smoke():
    s = chip_smoke.Smoke(out=open(os.devnull, "w"))
    yield s
    s.close()
    s.out.close()


def _run_off_chip(script: Path, cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


# -- the gates -------------------------------------------------------------
def test_smoke_off_chip_exits_nonzero_naming_the_backend():
    proc = _run_off_chip(REPO / "chip_smoke.py", REPO)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "needs a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result line


def test_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = _run_off_chip(tmp_path / "chip_smoke.py", tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_require_tpu_and_device_record():
    with pytest.raises(SystemExit, match="needs a TPU"):
        chip.require_tpu("a test")
    assert chip.device_record() == {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}


# -- the compile-cache rule --------------------------------------------------
@pytest.fixture
def restore_cache_config():
    """The CPU harness must not fill the in-checkout cache directory."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def test_cache_dir_from_the_environment_is_left_alone(
        monkeypatch, restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    chip.use_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_one_ignored_path_in_the_checkout(
        monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert chip.use_compile_cache() == chip.CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == chip.CACHE_DIR
    assert Path(chip.CACHE_DIR).parent == REPO
    ignored = (REPO / ".gitignore").read_text().split()
    assert Path(chip.CACHE_DIR).name + "/" in ignored


# -- the legs, at toy width --------------------------------------------------
def test_train_and_flat_scaler_legs_pass_at_toy_width(smoke):
    chip_smoke.train_leg(smoke, TOY)
    losses = smoke.record["train"]["losses"]
    assert len(losses) == TOY.train_steps and losses[-1] < losses[0]
    assert set(smoke.record["kernels"]["train_step"]) == set(
        chip_smoke.TRAIN_KERNELS)
    # 70 rows in 64-row blocks: the ragged last block is on the route
    chip_smoke.flat_scaler_leg(smoke, 70 * 1024, interpret=True)
    assert "flat_scaler_run" in smoke.record["sections"]


def test_serve_and_four_chip_legs_pass_at_toy_width(smoke):
    tokens = chip_smoke.serve_leg(smoke, TOY)
    assert [len(t) for t in tokens] == [TOY.new_tokens] * TOY.n_requests
    assert set(smoke.record["kernels"]) == {
        f"serve_tp1_{e}/{p}" for e, progs in (
            ("plain", ("decode", "chunk_prefill")),
            ("spec", ("decode", "chunk_prefill", "spec_verify")))
        for p in progs}
    # a wrong token is ~3 logit-sigmas below the dense forward's best
    wrong = [[(t + 1) % TOY.vocab for t in row] for row in tokens]
    with pytest.raises(chip_smoke.SmokeFailure, match="within"):
        chip_smoke._check_against_dense(
            smoke, TOY, chip_smoke._init_params(TOY.gpt_config()),
            "wrong_tokens", wrong)

    chip_smoke.four_chip_leg(smoke, TOY, tokens)
    assert smoke.record["dp_train"]["params_and_opt_state_on"] == [0, 1, 2, 3]
    tp4 = smoke.record["serve_tp4"]
    assert tp4["placement"]["serve_tp4_plain"]["kv_pool_on"] == [0, 1, 2, 3]
    # byte-identical on the CPU mesh (PR 16's pin); not required on a chip
    assert tp4["identical_to_one_chip"] == TOY.n_requests


def test_kernel_assertion_goes_red_when_a_gate_disables_kernels(
        smoke, monkeypatch):
    """Model code takes the XLA path silently; the smoke must not."""
    from apex_tpu.analysis import kernel_inventory

    monkeypatch.setenv("APEX_TPU_DISABLE_PALLAS", "1")
    step, state = chip_smoke.train_program(TOY)
    inventory = kernel_inventory(step, *state, 1.0)
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="apex_tpu_residual_ln_fwd"):
        chip_smoke.require_kernels(smoke, "train_step", inventory,
                                   chip_smoke.TRAIN_KERNELS, interpret=True)
    # and a kernel handed to the wrong executor is just as red
    monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS")
    step, state = chip_smoke.train_program(TOY)  # re-trace: jit caches
    inventory = kernel_inventory(step, *state, 1.0)
    with pytest.raises(chip_smoke.SmokeFailure, match="interpreted"):
        chip_smoke.require_kernels(smoke, "train_step", inventory,
                                   chip_smoke.TRAIN_KERNELS, interpret=False)
    # a kernel the chip does run (the tails are XLA's there): its gate
    # saying no is red on the chip's own list too
    on_chip = chip_smoke.train_kernels(
        dataclasses.replace(TOY, interpret=False))
    assert "apex_tpu_flash_fwd" in on_chip["names"]
    flashless = [r for r in inventory if r.name != "apex_tpu_flash_fwd"]
    with pytest.raises(chip_smoke.SmokeFailure, match="apex_tpu_flash_fwd"):
        chip_smoke.require_kernels(smoke, "train_step", flashless,
                                   on_chip["names"], interpret=True)


@pytest.mark.parametrize("interpret", [True, False])
def test_train_kernels_follow_the_tails_gate(smoke, monkeypatch, interpret):
    """What the smoke expects of the train step is what the gate engages:
    interpreted, all nine; compiled for a TPU, flash and the packed sweep,
    and a tail kernel in the trace is as red as a missing one."""
    from apex_tpu.analysis import kernel_inventory

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    size = dataclasses.replace(TOY, interpret=interpret)
    expected = chip_smoke.train_kernels(size)
    if interpret:
        assert expected == {"names": chip_smoke.TRAIN_KERNELS, "absent": ()}
        return
    assert expected == {"names": chip_smoke.CHIP_TRAIN_KERNELS,
                        "absent": chip_smoke.TAIL_KERNELS}
    assert set(expected["names"]) == {
        "apex_tpu_flash_fwd", "apex_tpu_flash_bwd_dkv",
        "apex_tpu_packed_adam"}
    step, state = chip_smoke.train_program(TOY)  # interpreted: tails in it
    inventory = kernel_inventory(step, *state, 1.0)
    with pytest.raises(chip_smoke.SmokeFailure, match="do not engage"):
        chip_smoke.require_kernels(smoke, "train_step", inventory,
                                   interpret=True, **expected)
