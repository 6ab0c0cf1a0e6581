"""apex_tpu.analysis + tools/static_audit.py: the jaxpr step auditor.

One red test per rule family (seeded violation -> expected finding,
with a golden-JSON fixture pinning the report schema) plus green
self-audit tests asserting the repo's own hot paths — the headline GPT
step, the packed FusedAdam/LAMB steps, the telemetry drain path —
produce zero error-severity findings. Tier-1: this file IS the CI wiring
for ``tools/static_audit.py --self`` (``not slow``, pure CPU tracing).
"""
import copy
import json
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from apex_tpu import analysis, telemetry  # noqa: E402
from apex_tpu.analysis import (  # noqa: E402
    assert_step_clean,
    audit_step,
    check_pack_spec,
)
from apex_tpu.multi_tensor_apply.packing import ROW, PackSpec  # noqa: E402
from apex_tpu.optimizers import FusedAdam  # noqa: E402
from tools import static_audit  # noqa: E402

GOLDEN = Path(__file__).parent / "data" / "static_audit_golden.json"


def _codes(report, severity=None):
    return [f.code for f in report.findings
            if severity is None or f.severity == severity]


# ---------------------------------------------------------------------------
# rule 1: donation / aliasing
# ---------------------------------------------------------------------------
def _packed_setup():
    params = {f"w{i}": jnp.zeros((4096,), jnp.bfloat16) for i in range(4)}
    grads = {k: jnp.full((4096,), 1e-3, jnp.bfloat16) for k in params}
    opt = FusedAdam(lr=1e-3, master_weights=True, packed=True,
                    packed_chunk_size=4096, packed_interpret=True)
    return params, grads, opt, opt.init(params)


def test_donation_red_undonated_packed_state():
    params, grads, opt, state = _packed_setup()
    step = jax.jit(lambda g, s, p: opt.step(g, s, p))  # NO donation
    rep = audit_step(step, grads, state, params, min_bytes=4096)
    assert "undonated_state" in _codes(rep, "error")
    # the finding names the argnum to donate
    f = [x for x in rep.errors if x.code == "undonated_state"][0]
    assert f.data["argnum"] == 1 and f.data["bytes"] > 0


def test_donation_flags_all_shadowed_carries():
    """When grads and params share an aval and NOTHING is donated, both
    must be named — neither may shadow the other (donating either gives
    the param output an in-place home)."""
    params, grads, opt, state = _packed_setup()
    step = jax.jit(lambda g, s, p: opt.step(g, s, p))
    rep = audit_step(step, grads, state, params, min_bytes=4096)
    flagged = {f.data["argnum"] for f in rep.findings
               if f.code in ("undonated_state", "undonated_carry")}
    assert {0, 1, 2} <= flagged


def test_donation_green_packed_state_donated():
    params, grads, opt, state = _packed_setup()
    step = jax.jit(lambda g, s, p: opt.step(g, s, p), donate_argnums=(1, 2))
    rep = assert_step_clean(step, grads, state, params, min_bytes=4096)
    assert rep.ok and "undonated_state" not in rep.codes()


def test_donation_plain_fn_donate_argnums_spelling():
    """Un-jitted step + explicit donate_argnums= (the jax.jit spelling)."""
    params, grads, opt, state = _packed_setup()
    fn = lambda g, s, p: opt.step(g, s, p)  # noqa: E731
    bad = audit_step(fn, grads, state, params, min_bytes=4096)
    good = audit_step(fn, grads, state, params, min_bytes=4096,
                      donate_argnums=(1, 2))
    assert "undonated_state" in _codes(bad, "error")
    assert good.ok


def test_donation_red_double_donation():
    x = jnp.zeros((65536,), jnp.float32)
    step = jax.jit(lambda a, b: (a + 1.0, b * 2.0), donate_argnums=(0, 1))
    rep = audit_step(step, x, x)  # same buffer donated twice
    assert "double_donation" in _codes(rep, "error")


def test_donation_green_master_copy_guard():
    """packed_init's copy=True guard: a single fp32 leaf of exact
    chunk-multiple size would alias its master without it (the
    no_update_mv hazard, optimizers/_packed.py) — donation must be clean."""
    params = {"w": jnp.zeros((4096,), jnp.float32)}
    opt = FusedAdam(lr=1e-3, master_weights=True, packed=True,
                    packed_chunk_size=4096, packed_interpret=True)
    state = opt.init(params)
    grads = {"w": jnp.zeros((4096,), jnp.float32)}
    step = jax.jit(lambda g, s, p: opt.step(g, s, p), donate_argnums=(1, 2))
    rep = audit_step(step, grads, state, params, min_bytes=4096)
    assert "double_donation" not in rep.codes()


def test_donation_red_pallas_without_aliases():
    def k(x_ref, o_ref):
        o_ref[:] = x_ref[:] * 2.0

    def make_step(scope):
        @jax.named_scope(scope)
        def step(x):
            return pl.pallas_call(
                k, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                interpret=True)(x)

        return step

    x = jnp.zeros((64, ROW), jnp.float32)
    # the packed/multi-tensor family contract is in-place: warning
    rep = audit_step(make_step("apex_tpu.packed_seeded"), x,
                     min_bytes=4096)
    assert "pallas_no_alias" in _codes(rep, "warning")
    # other kernels are often deliberately out-of-place: informational
    rep = audit_step(make_step("apex_tpu.some_attention"), x,
                     min_bytes=4096)
    assert "pallas_no_alias" in _codes(rep, "info")


# ---------------------------------------------------------------------------
# rule 2: host-sync discipline
# ---------------------------------------------------------------------------
def test_host_sync_red_ungated_callback():
    def step(x):
        jax.debug.callback(lambda v: None, x)
        return x * 2.0

    rep = audit_step(step, jnp.zeros((8,)))
    assert "ungated_callback" in _codes(rep, "error")


def test_host_sync_red_callback_in_scan():
    def step(x):
        def body(c, t):
            jax.debug.callback(lambda v: None, c)
            return c * t, c

        y, _ = jax.lax.scan(body, x, jnp.arange(4.0))
        return y

    rep = audit_step(step, jnp.float32(1))
    codes = rep.codes()
    assert "callback_in_scan" in codes and "ungated_callback" in codes


def test_host_sync_red_ordered_io_callback():
    from jax.experimental import io_callback

    def step(x):
        io_callback(lambda v: None, None, x, ordered=True)
        return x + 1.0

    rep = audit_step(step, jnp.zeros((8,)))
    assert "ordered_io_callback" in _codes(rep, "error")


def test_host_sync_green_cond_gated_drain():
    """The telemetry drain path: the callback lives under lax.cond, so
    the audit must be silent (the sync-free discipline holds)."""
    sink = telemetry.NullRecorder()

    def step(m, loss):
        m = telemetry.accumulate(m, loss=loss, tokens=64)
        m = telemetry.drain(m, sink, every_n=10)
        return m, loss * 0.5

    rep = assert_step_clean(
        jax.jit(step, donate_argnums=(0,)),
        telemetry.init_metrics(), jnp.float32(0))
    assert not rep.by_rule("host_sync")


# ---------------------------------------------------------------------------
# rule 3: amp dtype flow
# ---------------------------------------------------------------------------
def test_dtype_red_fp32_matmul_in_bf16_step():
    def step(x16, w16, m32):
        y = (x16 @ w16).astype(jnp.float32)
        z = m32 @ m32  # the leak: a large fp32 GEMM in a bf16 step
        return y.sum() + z.sum()

    args = (jnp.zeros((256, 256), jnp.bfloat16),
            jnp.zeros((256, 256), jnp.bfloat16),
            jnp.zeros((256, 256), jnp.float32))
    rep = audit_step(step, *args, compute_dtype="bfloat16", min_bytes=1024)
    assert "fp32_matmul" in _codes(rep, "warning")
    strict = audit_step(step, *args, compute_dtype="bfloat16",
                        min_bytes=1024, strict_dtype=True)
    assert "fp32_matmul" in _codes(strict, "error")


def test_dtype_policy_inferred_from_matmul_mix():
    """With equal bf16/f32 matmul weight the step reads as
    low-precision-intent and the f32 dot is flagged without an explicit
    compute_dtype."""
    def step(x16, w16, m32):
        return (x16 @ w16).astype(jnp.float32).sum() + (m32 @ m32).sum()

    rep = audit_step(step, jnp.zeros((256, 256), jnp.bfloat16),
                     jnp.zeros((256, 256), jnp.bfloat16),
                     jnp.zeros((256, 256), jnp.float32), min_bytes=1024)
    assert "fp32_matmul" in rep.codes()


def test_dtype_green_pure_fp32_step():
    def step(a, b):
        return (a @ b).sum()

    rep = audit_step(step, jnp.zeros((128, 128)), jnp.zeros((128, 128)))
    assert not rep.by_rule("dtype_flow")


def test_dtype_red_double_cast():
    def step(x):
        y = jnp.exp(x)  # a live f32 value, not a fresh matmul output
        return y.astype(jnp.bfloat16).astype(jnp.float32) * 2.0

    rep = audit_step(step, jnp.zeros((65536,), jnp.float32),
                     compute_dtype="bfloat16")
    assert "double_cast" in _codes(rep, "warning")


def test_double_cast_inside_pallas_body_not_flagged():
    """Kernel bodies are opaque (walk._OPAQUE): ref arithmetic inside a
    pallas_call must not leak whole-program dtype findings."""
    def k(x_ref, o_ref):
        y = x_ref[:].astype(jnp.float32) * 2.0
        o_ref[:] = y.astype(jnp.bfloat16).astype(jnp.float32)

    @jax.named_scope("apex_tpu.packed_casty")
    def step(x):
        return pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            input_output_aliases={0: 0}, interpret=True)(x)

    rep = audit_step(step, jnp.zeros((64, ROW), jnp.float32),
                     compute_dtype="bfloat16")
    assert "double_cast" not in rep.codes()


def test_dtype_matmul_rail_truncation_not_flagged():
    """Truncating a fresh MXU accumulation to the bf16 rail (and its
    AD-transposed upcast twin) is amp policy, not a double-cast."""
    def step(x16, w16):
        y = jnp.einsum("ij,jk->ik", x16, w16,
                       preferred_element_type=jnp.float32)
        return y.astype(jnp.bfloat16).astype(jnp.float32).sum()

    rep = audit_step(step, jnp.zeros((256, 256), jnp.bfloat16),
                     jnp.zeros((256, 256), jnp.bfloat16), min_bytes=1024)
    assert "double_cast" not in rep.codes()


# ---------------------------------------------------------------------------
# rule 4: constant bloat & recompile hazards
# ---------------------------------------------------------------------------
def test_constants_red_large_baked_constant():
    big = np.ones((512, 1024), np.float32)  # 2 MiB closure capture

    def step(x):
        return x * jnp.asarray(big).sum()

    rep = audit_step(step, jnp.float32(3))
    assert "large_constant" in _codes(rep, "warning")
    f = [x for x in rep.findings if x.code == "large_constant"][0]
    assert f.data["bytes"] == big.nbytes


def test_constants_error_at_hbm_scale():
    big = np.ones((512, 1024), np.float32)

    def step(x):
        return x * jnp.asarray(big).sum()

    rep = audit_step(step, jnp.float32(3), const_bytes_error=1 << 20)
    assert "large_constant" in _codes(rep, "error")


def test_constants_red_weak_type_input():
    rep = audit_step(lambda x: x * 2.0, 3.0)  # Python scalar arg
    assert "weak_type_input" in _codes(rep, "warning")
    strong = audit_step(lambda x: x * 2.0, jnp.float32(3))
    assert "weak_type_input" not in strong.codes()


# ---------------------------------------------------------------------------
# rule 5: PackSpec invariants
# ---------------------------------------------------------------------------
def test_packing_green_spec():
    spec = PackSpec({"a": jnp.zeros((2048,)), "b": jnp.zeros((100,))},
                    chunk_size=ROW)
    assert check_pack_spec(spec) == []


def test_packing_red_misaligned_offsets():
    spec = PackSpec({"a": jnp.zeros((2048,)), "b": jnp.zeros((100,))},
                    chunk_size=ROW)
    bad = copy.copy(spec)
    bad.offsets = (0, 2100)  # not ROW-aligned, overlaps a's padded extent
    codes = [f.code for f in check_pack_spec(bad)]
    assert "misaligned_offset" in codes
    assert all(f.severity == "error" for f in check_pack_spec(bad))


def test_packing_red_truncated_leaf_tables():
    """A leaf with no offset entry at all must not audit clean (zip over
    the per-leaf tuples would silently drop the unmatched tail)."""
    spec = PackSpec({"a": jnp.zeros((2048,)), "b": jnp.zeros((100,))},
                    chunk_size=ROW)
    bad = copy.copy(spec)
    bad.offsets = spec.offsets[:-1]
    assert "inconsistent_leaf_tables" in [
        f.code for f in check_pack_spec(bad)]


def test_packing_red_total_not_chunk_multiple():
    spec = PackSpec({"a": jnp.zeros((2048,))}, chunk_size=ROW)
    bad = copy.copy(spec)
    bad.total = spec.total + 1
    assert "total_not_chunk_multiple" in [
        f.code for f in check_pack_spec(bad)]


def test_packing_shard_alignment_precondition():
    """The ROADMAP sharded-packed follow-on needs ROW-aligned equal
    shards; the checker prices both failure modes."""
    spec = PackSpec({"a": jnp.zeros((3 * ROW,))}, chunk_size=ROW)
    assert check_pack_spec(spec, shard_count=3) == []
    assert "shard_unaligned_total" in [
        f.code for f in check_pack_spec(spec, shard_count=5)]
    wide = PackSpec({"a": jnp.zeros((2 * ROW,))}, chunk_size=2 * ROW)
    bad = copy.copy(wide)
    bad.total = 2 * ROW  # divisible by 4 shards, but ROW/2 per shard
    assert "shard_not_row_aligned" in [
        f.code for f in check_pack_spec(bad, shard_count=4)]


def test_packing_rule_picks_spec_from_packed_state():
    params, grads, opt, state = _packed_setup()
    bad_state = copy.copy(state)
    bad_spec = copy.copy(state.spec)
    bad_spec.offsets = tuple(o + 1 for o in bad_spec.offsets[1:]) + (3,)
    bad_state.spec = bad_spec
    step = jax.jit(lambda g, s, p: opt.step(g, s, p), donate_argnums=(1, 2))
    rep = audit_step(step, grads, state, params, rules=("packing",),
                     pack_specs=[bad_spec])
    assert "misaligned_offset" in _codes(rep, "error")


# ---------------------------------------------------------------------------
# scope coverage
# ---------------------------------------------------------------------------
def test_scopes_red_unscoped_pallas_kernel():
    def k(x_ref, o_ref):
        o_ref[:] = x_ref[:] * 2.0

    def step(x):  # no jax.named_scope("apex_tpu....")
        return pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            input_output_aliases={0: 0}, interpret=True)(x)

    rep = audit_step(step, jnp.zeros((8, ROW), jnp.float32))
    assert "unscoped_kernel" in _codes(rep, "warning")


def test_scopes_green_packed_kernels_are_scoped():
    params, grads, opt, state = _packed_setup()
    step = jax.jit(lambda g, s, p: opt.step(g, s, p), donate_argnums=(1, 2))
    rep = audit_step(step, grads, state, params, rules=("scopes",))
    assert "unscoped_kernel" not in rep.codes()


def _layered_step(loss_scoped: bool):
    def step(x, w):
        def loss(w):
            with jax.named_scope("apex_tpu.mlp"):
                h = jnp.tanh(x @ w)
            if loss_scoped:
                with jax.named_scope("apex_tpu.cross_entropy"):
                    return jnp.sum(h * h)
            return jnp.sum(h * h)            # under no layer scope
        l, g = jax.value_and_grad(loss)(w)
        with jax.named_scope("apex_tpu.optimizer_step"):
            w = w - 0.1 * g
        return w, l
    return jax.jit(step), (jnp.ones((8, 8)), jnp.ones((8, 8)))


def test_scopes_red_equations_outside_every_layer_scope():
    step, args = _layered_step(loss_scoped=False)
    rep = audit_step(step, *args, rules=("scopes",))
    (f,) = [x for x in rep.findings if x.code == "unscoped_layer"]
    assert f.severity == "info"
    # the loss's forward and backward: mul, reduce_sum and their transposes
    assert f.data["outside"] >= 3 and f.data["scoped"] >= 6
    assert "apex_tpu." not in f.where


def test_scopes_green_every_equation_under_a_layer_scope():
    step, args = _layered_step(loss_scoped=True)
    rep = audit_step(step, *args, rules=("scopes",))
    assert "unscoped_layer" not in rep.codes()


def test_scopes_silent_on_a_step_that_names_no_layer():
    rep = audit_step(jax.jit(lambda x: x * 2.0 + 1.0), jnp.ones((8,)),
                     rules=("scopes",))
    assert "unscoped_layer" not in rep.codes()


# ---------------------------------------------------------------------------
# golden JSON fixture: the report schema is pinned byte-for-byte
# ---------------------------------------------------------------------------
def seeded_violation_report():
    """One deterministic step violating every rule family at once."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu.analysis import CollectiveBudget

    big = np.ones((300, 1024), np.float32)  # ~1.2 MiB baked constant
    # one-device mesh: the traced shard_map (and its psums) is identical
    # on the 8-device harness and a standalone 1-device run
    mesh = Mesh(np.array(jax.devices()[:1]), ("tensor",))

    def unscoped_kernel(x_ref, o_ref):
        o_ref[:] = x_ref[:] * 2.0

    def tp_body(a, b):
        t = jax.lax.psum(a @ b, "tensor")
        return jax.lax.psum(t, "tensor")  # unpaired double reduction

    tp = shard_map(tp_body, mesh=mesh, in_specs=(P(), P()),
                   out_specs=P(), check_vma=False)

    def step(state, x16, w16, scale):
        jax.debug.callback(lambda v: None, x16)       # ungated callback
        y = x16 @ w16                                  # bf16 policy GEMM
        z = state["exp_avg"] @ state["exp_avg"]        # fp32 leak
        z = pl.pallas_call(                            # unscoped kernel
            unscoped_kernel,
            out_shape=jax.ShapeDtypeStruct(z.shape, z.dtype),
            input_output_aliases={0: 0}, interpret=True)(z)
        out = (y.astype(jnp.float32).sum() + z.sum()
               + jnp.asarray(big).sum()
               + tp(x16, w16).sum().astype(jnp.float32)) * scale
        return {"exp_avg": state["exp_avg"] * 0.9}, out  # carried, undonated

    args = ({"exp_avg": jnp.ones((256, 256), jnp.float32)},
            jnp.ones((256, 256), jnp.bfloat16),
            jnp.ones((256, 256), jnp.bfloat16),
            3.0)                                       # weak-type scalar
    corrupt = PackSpec({"a": jnp.zeros((2048,)), "b": jnp.zeros((100,))},
                       chunk_size=ROW)
    corrupt = copy.copy(corrupt)
    corrupt.offsets = (0, 2100)                        # mid-row offset
    return audit_step(step, *args, name="seeded", min_bytes=1024,
                      pack_specs=[corrupt],
                      # budget declares ONE psum over no axes: the body's
                      # two tensor-axis psums land over_budget + unknown
                      collective_budget=CollectiveBudget(
                          counts={"psum": 1}, axes=()),
                      # the replicated bf16 GEMM operands (128 KiB each)
                      # trip the scouting warning at this threshold
                      replicated_bytes=1 << 16)


def test_golden_fixture_matches():
    got = seeded_violation_report().to_dict()
    want = json.loads(GOLDEN.read_text())
    assert got == want, (
        "audit JSON drifted from the golden fixture; if the change is "
        "intentional, regenerate with:\n  python -c \"import json, "
        "tests.test_static_audit as t; print(json.dumps("
        "t.seeded_violation_report().to_dict(), indent=2))\" "
        "> tests/data/static_audit_golden.json")


def test_golden_fixture_covers_every_family():
    want = json.loads(GOLDEN.read_text())
    rules = {f["rule"] for f in want["findings"]}
    assert rules == {"donation", "host_sync", "dtype_flow", "constants",
                     "packing", "scopes", "collectives", "sharding"}
    assert want["ok"] is False


def test_audit_json_is_deterministic():
    a = seeded_violation_report().to_json()
    b = seeded_violation_report().to_json()
    assert a == b


# ---------------------------------------------------------------------------
# assert_step_clean gating
# ---------------------------------------------------------------------------
def test_assert_step_clean_raises_with_table():
    params, grads, opt, state = _packed_setup()
    step = jax.jit(lambda g, s, p: opt.step(g, s, p))  # undonated
    with pytest.raises(AssertionError, match="undonated_state"):
        assert_step_clean(step, grads, state, params, min_bytes=4096)


def test_assert_step_clean_severity_warning_gate():
    def step(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32) * 2.0

    x = jnp.zeros((65536,), jnp.float32)
    # double_cast is warning-severity: clean at the default error gate...
    assert_step_clean(step, x, compute_dtype="bfloat16")
    # ...but the warning gate trips on it
    with pytest.raises(AssertionError, match="double_cast"):
        assert_step_clean(step, x, compute_dtype="bfloat16",
                          severity="warning")


def test_unknown_rule_rejected():
    with pytest.raises(ValueError, match="unknown rules"):
        audit_step(lambda x: x, jnp.float32(0), rules=("no_such_rule",))


# ---------------------------------------------------------------------------
# self-audit: the repo's own hot paths are clean (tier-1 CI gate for
# tools/static_audit.py --self)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("target", sorted(static_audit.TARGETS))
def test_self_audit_target_clean(target):
    fn, args, kw = static_audit.TARGETS[target]()
    rep = assert_step_clean(fn, *args, name=target, **kw)
    assert rep.ok


def test_self_audit_cli_json_exit_zero(capsys):
    rc = static_audit.main(["--self", "--target", "telemetry_drain",
                            "--target", "packed_adam_step", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["ok"] is True
    assert set(out["targets"]) == {"telemetry_drain", "packed_adam_step"}


def test_self_audit_cli_exits_nonzero_on_errors(monkeypatch, capsys):
    def bad_target():
        params, grads, opt, state = _packed_setup()
        step = jax.jit(lambda g, s, p: opt.step(g, s, p))  # undonated
        return step, (grads, state, params), {"min_bytes": 4096}

    monkeypatch.setitem(static_audit.TARGETS, "seeded_bad", bad_target)
    rc = static_audit.main(["--self", "--target", "seeded_bad", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1 and out["ok"] is False


def test_self_audit_cli_fail_on_warning(monkeypatch, capsys):
    def warn_target():
        def step(x):
            y = jnp.exp(x)
            return y.astype(jnp.bfloat16).astype(jnp.float32)

        return (step, (jnp.zeros((65536,), jnp.float32),),
                {"compute_dtype": "bfloat16"})

    monkeypatch.setitem(static_audit.TARGETS, "warny", warn_target)
    assert static_audit.main(
        ["--self", "--target", "warny", "--json"]) == 0
    capsys.readouterr()
    assert static_audit.main(
        ["--self", "--target", "warny", "--json", "--fail-on",
         "warning"]) == 1
