"""The two rehearsals that cost no chip time.

1. The command end to end on the CPU at a tiny size (``--rehearse 1``):
   every line names the platform and no metric is reported; a real cell
   with no chip is a non-zero exit and no result.
2. Each one-chip cell's timed program compiled for the described v5e.

The TPU compiler is installed in the sandbox and compiles for a topology
that is described and not attached: it raises what the chip's compiler
would raise (a block shape Mosaic refuses, a program that does not fit)
before any chip time is spent. The tests compile at the published widths
and a cut depth (a whole-depth step takes a minute and more); the
whole-depth memory accounts are in PERF.md.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and a module that decides at import
whether its tests exist breaks collection under pytest-xdist.
"""
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import manifest as mf  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark import train_cell  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture()
def no_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _cell(name, layers=None):
    cell = mf.Cell(mf.load_manifest(), name)
    if layers is not None:
        key = "n_layer" if "n_layer" in cell.config else "num_hidden_layers"
        cell.config = {**cell.config, key: layers}
    return cell


def _as_shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def compile_train(topo, name, layers=None):
    """A one-chip cell's train step lowered for the described chip; returns
    the compiled program. State is built on the CPU (shapes only matter) with
    the library's TPU paths switched on."""
    cell = _cell(name, layers)
    assert cell.chips == 1, "the four-chip step is rehearsed on the CPU mesh"
    mix = train_cell.traffic.train_mix(cell.mix, cell.chips)
    one = SingleDeviceSharding(topo.devices[0])
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        program = train_cell.build_program(
            cell.config, cell.mix, 0, jax.devices("cpu")[:1])
        batch = jax.ShapeDtypeStruct((mix["batch"], mix["seq"]), jnp.int32,
                                     sharding=one)
        return program._jit.lower(
            *_as_shapes(program.state, one), batch, batch).compile()


def _gb(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes) / 1e9


@pytest.mark.parametrize("name", ["gpt2-345m.train-1chip",
                                  "bert-large.train-1chip"])
def test_train_step_compiles_for_v5e(topo, no_cache, name):
    compiled = compile_train(topo, name, layers=2)
    assert "tpu_custom_call" in compiled.as_text()
    assert 0 < _gb(compiled) < 16


# ---------------------------------------------------------------------------
# the command on the CPU
# ---------------------------------------------------------------------------
def test_a_real_cell_without_a_chip_exits_non_zero_and_prints_no_result(
        capsys):
    rc = harness.main(["--workload", "gpt2-345m.train-1chip", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0 and out.strip() == ""
    assert "[bench cpu]" in err and "needs 1 TPU chip" in err


def test_an_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        harness.main(["--workload", "no-such-cell", "--seed", "1",
                      "--seconds", "1"])


@pytest.mark.parametrize("name", ["gpt2-345m.train-1chip",
                                  "bert-large.train-1chip"])
def test_rehearsal_names_the_platform_and_reports_no_metric(capsys, name):
    import json

    rc = harness.main(["--workload", name,
                       "--seed", str(2 ** 31 + 77), "--seconds", "1",
                       "--trace", "1", "--rehearse", "1"])
    out, err = capsys.readouterr()
    assert rc == 0
    lines = [ln for ln in err.splitlines() if ln.startswith("[bench")]
    assert lines and all(ln.startswith("[bench cpu]") for ln in lines)
    line = json.loads(out.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert list(line)[-1] == "compared"      # the numbers compared come last
    assert {"process_to_window_s", "entry.host_start_s", "entry.compile_s",
            "entry.cache_misses", "entry.build_s", "entry.warm_s",
            "setup_s"} <= set(line["phases"])
    assert line["phases"]["compiled_in_window"] == 0
