"""Test harness: 8 virtual CPU devices, mirroring the reference's
single-node multi-process testing strategy (SURVEY.md §4,
``apex/transformer/testing/distributed_test_base.py``) — but SPMD: one
process, an 8-device mesh, deterministic seeds."""
import os

# Must run before jax initialises its backends: the device-count flag is
# read at backend start-up, and jax.config.update pins the platform even
# when the environment exports another one.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)

assert jax.default_backend() == "cpu" and len(jax.devices()) == 8, (
    "test harness expects 8 virtual CPU devices; got "
    f"{jax.default_backend()} x{len(jax.devices())}"
)


# ---------------------------------------------------------------------------
# Test tiers (the reference's L0-default vs full-suite split,
# ``tests/L0/run_test.py:29-33``): tests measured slow on the 8-device CPU
# harness are listed in tests/slow_tests.txt and marked ``slow`` here, so
#   python -m pytest tests/ -q -m "not slow"
# is the quick tier (~2 min) and the bare run is the full suite. New tests
# are quick by default; re-generate the list with --durations when a test
# grows past a few seconds.
# ---------------------------------------------------------------------------
import pathlib

import pytest as _pytest

_SLOW_LIST = pathlib.Path(__file__).parent / "slow_tests.txt"
_SLOW_IDS = frozenset(
    line.strip() for line in _SLOW_LIST.read_text().splitlines()
    if line.strip()
) if _SLOW_LIST.exists() else frozenset()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: measured slow on the CPU harness (excluded from "
        "the quick tier; see tests/slow_tests.txt)"
    )


def pytest_collection_modifyitems(config, items):
    matched = set()
    for item in items:
        nodeid = item.nodeid.replace("\\", "/")
        if not nodeid.startswith("tests/"):
            nodeid = "tests/" + nodeid
        if nodeid in _SLOW_IDS:
            matched.add(nodeid)
            item.add_marker(_pytest.mark.slow)
    # a renamed/re-parametrized slow test would silently re-enter the quick
    # tier; warn only about stale entries whose FILE was collected, so
    # partial runs (--ignore, single files) don't fire spuriously
    collected_files = {
        item.nodeid.replace("\\", "/").split("::")[0] for item in items
    }
    collected_files |= {"tests/" + f for f in collected_files}
    stale = {
        sid for sid in _SLOW_IDS - matched
        if sid.split("::")[0] in collected_files
    }
    node_selected = any("::" in str(a) for a in config.args)
    if stale and not config.getoption("-k") and not node_selected:
        import warnings

        warnings.warn(
            "tests/slow_tests.txt entries match no collected test "
            f"(rename/param drift?): {sorted(stale)[:5]}"
            + (" ..." if len(stale) > 5 else "")
        )
