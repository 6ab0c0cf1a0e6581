"""Unit tests for the xplane op-breakdown helpers (``apex_tpu.telemetry.tracing``).

The profiling capture itself needs a real TPU; the parsing/classification
logic is pure and pinned here so a refactor cannot silently misbucket a
step's breakdown. The golden xplane fixtures at the bottom build
REAL xplane protobufs (with tensorflow's protobuf classes where they are
installed; the parser itself reads them with ``jax.profiler.ProfileData``)
and pin the corrected category attribution end-to-end (round-5 VERDICT: generic ``%fusion.N`` ops were all booked as
"fusion(elementwise)", hiding the dense GEMMs — 42.7% of the GPT step
mislabeled).
"""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from apex_tpu.telemetry.tracing import (  # noqa: E402
    breakdown_table,
    categorize_op,
    parse_xspace_op_times,
    short_op_name,
)


def test_short_op_name_strips_hlo_decoration():
    assert short_op_name(
        "%convolution_tanh_fusion.3 = bf16[4096,4096]{1,0} fusion(...)"
    ) == "convolution_tanh_fusion"
    assert short_op_name("%while.7 = (s32[], f32[8]) while(...)") == "while"
    assert short_op_name(
        "%apex_tpu_flash_fwd.65 = (bf16[8,16,1024,64]) custom-call(...)"
    ) == "apex_tpu_flash_fwd"
    # no ' = ' (bare name) and no trailing index both survive
    assert short_op_name("%copy-done") == "copy-done"
    assert short_op_name("fusion") == "fusion"


def test_category_buckets():
    assert categorize_op("apex_tpu_flash_fwd") == "attention-kernel"
    assert categorize_op("apex_tpu.flash_attention") == "attention-kernel"
    assert categorize_op("convolution_add_fusion") == "matmul/conv"
    assert categorize_op("all-reduce-start") == "collective"
    assert categorize_op("collective-permute") == "collective"
    assert categorize_op("bitcast_dynamic-update-slice_fusion") == "data-movement"
    assert categorize_op("copy") == "data-movement"
    assert categorize_op("exponential_reduce_fusion") == "reduce"
    assert categorize_op("select_add_fusion") == "fusion(elementwise)"
    assert categorize_op("iota") == "other"


def test_category_hlo_category_stat_is_authoritative():
    """The profiler's per-op category (from the fused computation's root
    op) overrides the generic name — the round-5 fix."""
    assert categorize_op("fusion", "convolution fusion") == "matmul/conv"
    assert categorize_op("fusion", "loop fusion") == "fusion(elementwise)"
    assert categorize_op("fusion", "output fusion") == "fusion(elementwise)"
    assert categorize_op("fusion", "all-reduce fusion") == "collective"
    assert categorize_op("fusion", "reduce fusion") == "reduce"
    # a named fusion with a contradicting stat: the stat wins
    assert categorize_op("select_add_fusion", "convolution fusion") \
        == "matmul/conv"


def test_category_generic_fusion_without_signal_is_unattributed():
    """A bare %fusion.N with no hlo_category and no callee signal must
    NOT be claimed as elementwise — that is the exact round-5 bug."""
    assert categorize_op("fusion") == "fusion(unattributed)"
    assert categorize_op("loop_fusion") == "fusion(unattributed)"
    assert categorize_op("fused_computation") == "fusion(unattributed)"


def test_category_generic_fusion_salvaged_from_callee():
    raw = ("%fusion.3 = bf16[4,4]{1,0} fusion(%p0, %p1), kind=kOutput, "
           "calls=%convolution_fusion.3")
    assert categorize_op("fusion", None, raw) == "matmul/conv"
    raw2 = "%fusion.9 = f32[8] fusion(%p0), kind=kLoop, calls=%fused_computation.9"
    assert categorize_op("fusion", None, raw2) == "fusion(unattributed)"


# ---------------------------------------------------------------------------
# golden xplane fixtures: real protobufs, end-to-end through the parser
# ---------------------------------------------------------------------------

def _build_xplane(tmp_path, ops):
    """Write a minimal real .xplane.pb: ops = [(name, ps, category|None)]."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    xs = xplane_pb2.XSpace()
    plane = xs.planes.add()
    plane.name = "/device:TPU:0"
    cat_md = plane.stat_metadata[1]
    cat_md.id = 1
    cat_md.name = "hlo_category"
    line = plane.lines.add()
    line.name = "XLA Ops"
    for i, (name, ps, cat) in enumerate(ops, start=1):
        md = plane.event_metadata[i]
        md.id = i
        md.name = name
        ev = line.events.add()
        ev.metadata_id = i
        ev.duration_ps = ps
        if cat is not None:
            st = ev.stats.add()
            st.metadata_id = 1
            st.str_value = cat
    # a non-TPU plane that must be ignored
    host = xs.planes.add()
    host.name = "/host:CPU"
    hl = host.lines.add()
    hl.name = "XLA Ops"
    (tmp_path / "plugins").mkdir(exist_ok=True)
    out = tmp_path / "plugins" / "host.xplane.pb"
    out.write_bytes(xs.SerializeToString())
    return str(tmp_path)


GOLDEN_OPS = [
    # the round-5 shape: generic fusions dominated by a conv-rooted one
    ("fusion.1", 700_000, "convolution fusion"),
    ("fusion.2", 150_000, "loop fusion"),
    ("fusion.3", 50_000, None),                      # no stat: unattributed
    ("apex_tpu_flash_fwd.65", 80_000, "custom-call"),
    ("copy.4", 10_000, "copy"),
    ("while.9", 999_999, None),                      # container: excluded
    ("all-reduce.5", 10_000, "all-reduce"),
]

# the pinned golden table for GOLDEN_OPS at n_steps=1
GOLDEN_CATEGORIES = {
    "matmul/conv": 70.0,
    "fusion(elementwise)": 15.0,
    "fusion(unattributed)": 5.0,
    "attention-kernel": 8.0,
    "data-movement": 1.0,
    "collective": 1.0,
}


def test_golden_xplane_fixture_end_to_end(tmp_path):
    trace_dir = _build_xplane(tmp_path, GOLDEN_OPS)
    total, per_op = parse_xspace_op_times(trace_dir)
    assert total == 1_000_000  # container excluded
    assert per_op[("fusion", "matmul/conv")] == 700_000
    assert per_op[("fusion", "fusion(elementwise)")] == 150_000
    assert per_op[("fusion", "fusion(unattributed)")] == 50_000
    table = breakdown_table(total, per_op, n_steps=1, top=10)
    got = {cat: row["pct"] for cat, row in table["categories"].items()}
    assert got == pytest.approx(GOLDEN_CATEGORIES)
    # top op is the conv-rooted fusion, labeled as matmul/conv
    assert table["ops"][0]["op"] == "fusion"
    assert table["ops"][0]["category"] == "matmul/conv"
    assert table["ops"][0]["pct"] == pytest.approx(70.0)


def test_golden_xplane_ref_value_category(tmp_path):
    """hlo_category delivered via stat_metadata ref_value indirection
    (the other xplane encoding) must resolve identically."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    xs = xplane_pb2.XSpace()
    plane = xs.planes.add()
    plane.name = "/device:TPU:0"
    key_md = plane.stat_metadata[1]
    key_md.id = 1
    key_md.name = "hlo_category"
    val_md = plane.stat_metadata[2]
    val_md.id = 2
    val_md.name = "convolution fusion"
    md = plane.event_metadata[1]
    md.id = 1
    md.name = "fusion.7"
    line = plane.lines.add()
    line.name = "XLA Ops"
    ev = line.events.add()
    ev.metadata_id = 1
    ev.duration_ps = 42_000
    st = ev.stats.add()
    st.metadata_id = 1
    st.ref_value = 2
    out = tmp_path / "t.xplane.pb"
    out.write_bytes(xs.SerializeToString())
    total, per_op = parse_xspace_op_times(str(tmp_path))
    assert total == 42_000
    assert per_op == {("fusion", "matmul/conv"): 42_000}
