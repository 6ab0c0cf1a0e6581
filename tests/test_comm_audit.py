"""apex_tpu.analysis collectives & sharding rules (ISSUE-19).

Red tests: one seeded violation per new rule family (over-budget psum,
vanished psum, undeclared axis, oversized gather, cond-divergent
collective, unbucketed loop reductions, indivisible/unknown/duplicate
shard specs, broken Megatron psum pairing). Green tests: the repo's own
tensor-parallel serving programs and the bucketed DDP step reproduce
their pinned communication budgets *statically* via ``comm_volume``, and
self-audit clean with the collective/sharding rules on.

Everything here is jaxpr tracing on the 8-virtual-CPU-device harness —
no execution, no kernels.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from apex_tpu.analysis import (  # noqa: E402
    CollectiveBudget,
    assert_step_clean,
    audit_step,
    check_collective_budget,
    check_shard_specs,
    collective_inventory,
    comm_volume,
)
from apex_tpu.parallel import DistributedDataParallel, GradBuckets  # noqa: E402
from tools import static_audit  # noqa: E402


def _mesh(*axes, shape=None):
    devs = np.array(jax.devices()[: int(np.prod(shape or [8]))])
    return Mesh(devs.reshape(shape or (8,)), axes)


def _codes(findings, severity=None):
    return [f.code for f in findings
            if severity is None or f.severity == severity]


def _inventory(fn, *args):
    return collective_inventory(jax.make_jaxpr(fn)(*args).jaxpr)


# ---------------------------------------------------------------------------
# comm_volume: the structured inventory
# ---------------------------------------------------------------------------
def test_comm_volume_counts_axes_and_bytes():
    mesh = _mesh("data")

    def body(x):
        y = jax.lax.psum(x, "data")             # out: 16*4 B
        g = jax.lax.all_gather(y, "data")       # out: 8*16*4 B
        return g.sum()

    f = shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P(),
                  check_vma=False)
    vol = comm_volume(f, jnp.zeros((128,), jnp.float32))
    assert vol["psum"] == {"count": 1, "bytes": 64, "axes": ["data"]}
    assert vol["all_gather"] == {"count": 1, "bytes": 512, "axes": ["data"]}


def test_comm_volume_counts_loop_bodies_once():
    """Static program shape: a psum inside a scan body is ONE eqn —
    the convention the serving 3-psum pin is stated in."""
    mesh = _mesh("data")

    def body(x):
        def it(c, t):
            return c + jax.lax.psum(t, "data"), ()

        c, _ = jax.lax.scan(it, jnp.float32(0), x)
        return c

    f = shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P(),
                  check_vma=False)
    vol = comm_volume(f, jnp.zeros((64,), jnp.float32))
    assert vol["psum"]["count"] == 1


def test_comm_volume_abstract_args():
    """ShapeDtypeStruct args trace without any real buffers."""
    mesh = _mesh("data")
    f = shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                  in_specs=P("data"), out_specs=P(), check_vma=False)
    vol = comm_volume(f, jax.ShapeDtypeStruct((64,), jnp.bfloat16))
    assert vol["psum"]["count"] == 1 and vol["psum"]["bytes"] == 16


# ---------------------------------------------------------------------------
# collective budgets: red, one per failure mode
# ---------------------------------------------------------------------------
def _psum_program():
    mesh = _mesh("data")
    f = shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                  in_specs=P("data"), out_specs=P(), check_vma=False)
    return f, (jnp.zeros((64,), jnp.float32),)


def test_budget_red_over_budget_psum():
    fn, args = _psum_program()
    rep = audit_step(fn, *args,
                     collective_budget=CollectiveBudget(counts={}))
    assert "over_budget_collective" in _codes(rep.findings, "error")
    f = [x for x in rep.errors if x.code == "over_budget_collective"][0]
    assert f.data == {"collective": "psum", "budget": 0, "actual": 1}


def test_budget_red_missing_collective():
    """Exact pin: a vanished reduction is a numerics hazard, not a win."""
    fn, args = _psum_program()
    rep = audit_step(fn, *args,
                     collective_budget=CollectiveBudget(
                         counts={"psum": 1, "all_gather": 1}))
    assert "missing_collective" in _codes(rep.findings, "error")


def test_budget_red_unknown_axis():
    fn, args = _psum_program()  # psums over "data"
    rep = audit_step(fn, *args,
                     collective_budget=CollectiveBudget(
                         counts={"psum": 1}, axes=("tensor",)))
    assert "unknown_axis_collective" in _codes(rep.findings, "error")


def test_budget_red_oversized_gather():
    mesh = _mesh("data")
    f = shard_map(lambda x: jax.lax.all_gather(x, "data"), mesh=mesh,
                  in_specs=P("data"), out_specs=P(None, "data"),
                  check_vma=False)
    x = jnp.zeros((8 * 1024,), jnp.float32)  # gathered output: 32 KiB
    rep = audit_step(f, x, collective_budget=CollectiveBudget(
        max_gather_bytes=1 << 14))
    assert "oversized_gather" in _codes(rep.findings, "error")
    ok = audit_step(f, x, collective_budget=CollectiveBudget(
        max_gather_bytes=1 << 20))
    assert "oversized_gather" not in ok.codes()


def test_budget_green_matching_pin():
    fn, args = _psum_program()
    rep = assert_step_clean(
        fn, *args, collective_budget=CollectiveBudget(
            counts={"psum": 1}, axes=("data",)))
    assert rep.ok


def test_check_collective_budget_standalone():
    fn, args = _psum_program()
    inv = _inventory(fn, *args)
    bad = check_collective_budget(inv, CollectiveBudget(counts={}))
    assert _codes(bad) == ["over_budget_collective"]
    assert check_collective_budget(
        inv, CollectiveBudget(counts={"psum": 1}, axes=("data",))) == []


# ---------------------------------------------------------------------------
# SPMD divergence lints
# ---------------------------------------------------------------------------
def test_red_cond_divergent_collective():
    mesh = _mesh("data")

    def body(x):
        return jax.lax.cond(
            x.sum() > 0,
            lambda v: jax.lax.psum(v, "data"),  # collective in ONE branch
            lambda v: v * 2.0,
            x)

    f = shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                  check_vma=False)
    rep = audit_step(f, jnp.zeros((64,), jnp.float32))
    assert "cond_divergent_collective" in _codes(rep.findings, "warning")
    br = [x for x in rep.findings
          if x.code == "cond_divergent_collective"][0].data["branches"]
    assert {"psum@data": 1} in br and {} in br


def test_green_cond_with_matching_branches():
    mesh = _mesh("data")

    def body(x):
        return jax.lax.cond(
            x.sum() > 0,
            lambda v: jax.lax.psum(v, "data") * 2.0,
            lambda v: jax.lax.psum(v, "data") * 0.5,
            x)

    f = shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                  check_vma=False)
    rep = audit_step(f, jnp.zeros((64,), jnp.float32))
    assert "cond_divergent_collective" not in rep.codes()


def test_red_unbucketed_loop_collectives():
    """Per-leaf psums in a scan body — the anti-pattern GradBuckets
    exists to kill — trip the hoist-and-bucket warning."""
    mesh = _mesh("data")

    def body(xs):
        def it(c, t):
            # four per-leaf reductions per iteration
            return c + sum(jax.lax.psum(t * k, "data")
                           for k in (1.0, 2.0, 3.0, 4.0)), ()

        c, _ = jax.lax.scan(it, jnp.float32(0), xs)
        return c

    f = shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P(),
                  check_vma=False)
    rep = audit_step(f, jnp.zeros((64,), jnp.float32))
    hits = [x for x in rep.findings
            if x.code == "unbucketed_loop_collectives"]
    assert hits and hits[0].severity == "warning"
    assert hits[0].data["count"] == 4 and hits[0].data["axes"] == "data"


# ---------------------------------------------------------------------------
# sharding specs
# ---------------------------------------------------------------------------
def test_red_indivisible_shard_dim():
    """jax itself raises at trace time on this layout; the standalone
    checker is the pre-trace gate the mesh-rebase workflow runs."""
    bad = check_shard_specs({"data": 8}, [P("data")], shapes=[(63,)])
    assert _codes(bad, "error") == ["indivisible_shard_dim"]
    assert bad[0].data["dim_size"] == 63 and bad[0].data["factor"] == 8
    assert check_shard_specs({"data": 8}, [P("data")], shapes=[(64,)]) == []


def test_red_unknown_mesh_axis_spec():
    bad = check_shard_specs({"data": 8}, [P("model")])
    assert "unknown_mesh_axis" in _codes(bad, "error")


def test_red_duplicate_mesh_axis_spec():
    bad = check_shard_specs({"data": 8}, [P("data", "data")],
                            shapes=[(64, 64)])
    assert "duplicate_mesh_axis" in _codes(bad, "error")


def test_check_shard_specs_accepts_real_mesh_and_multi_axis():
    mesh = _mesh("dp", "tp", shape=(4, 2))
    assert check_shard_specs(mesh, [P(("dp", "tp"), None)],
                             shapes=[(16, 32)]) == []
    bad = check_shard_specs(mesh, [P(("dp", "tp"), None)],
                            shapes=[(12, 32)])  # 12 % 8 != 0
    assert "indivisible_shard_dim" in _codes(bad)


def test_red_unpaired_psum_tail():
    """psum(psum(x @ w)) over the same axis with no GEMM between — the
    classic double-reduction tensor-parallel bug."""
    mesh = _mesh("tensor")

    def body(x, w):
        y = jax.lax.psum(x @ w, "tensor")
        return jax.lax.psum(y * 2.0, "tensor")  # already reduced!

    f = shard_map(body, mesh=mesh,
                  in_specs=(P(None, "tensor"), P("tensor", None)),
                  out_specs=P(), check_vma=False)
    rep = audit_step(f, jnp.zeros((16, 64), jnp.float32),
                     jnp.zeros((64, 16), jnp.float32))
    assert "unpaired_psum_tail" in _codes(rep.findings, "warning")


def test_green_column_row_psum_pairing():
    """The legal Megatron shape: column GEMM -> row GEMM -> one psum."""
    mesh = _mesh("tensor")

    def body(x, wc, wr):
        y = x @ wc                    # column-parallel (no comm)
        z = jnp.tanh(y) @ wr          # row-parallel partial
        return jax.lax.psum(z, "tensor")  # exactly one tail

    f = shard_map(body, mesh=mesh,
                  in_specs=(P(), P(None, "tensor"), P("tensor", None)),
                  out_specs=P(), check_vma=False)
    rep = audit_step(f, jnp.zeros((16, 64), jnp.float32),
                     jnp.zeros((64, 32), jnp.float32),
                     jnp.zeros((32, 64), jnp.float32))
    assert "unpaired_psum_tail" not in rep.codes()


def test_red_large_replicated_operand():
    mesh = _mesh("data")

    def body(w, x):
        return (x @ w).sum()

    f = shard_map(body, mesh=mesh, in_specs=(P(), P("data", None)),
                  out_specs=P(), check_vma=False)
    w = jnp.zeros((512, 512), jnp.float32)  # 1 MiB, replicated
    x = jnp.zeros((64, 512), jnp.float32)
    rep = audit_step(f, w, x)
    hits = [h for h in rep.findings
            if h.code == "large_replicated_operand"]
    assert hits and hits[0].severity == "warning"
    assert hits[0].data["bytes"] == 512 * 512 * 4
    # raising the threshold silences the scouting report
    quiet = audit_step(f, w, x, replicated_bytes=1 << 24)
    assert "large_replicated_operand" not in quiet.codes()


# ---------------------------------------------------------------------------
# deep nesting: the inventory (and _contains_prim) see through
# shard_map -> scan -> cond -> pjit stacks of any depth
# ---------------------------------------------------------------------------
def _deeply_nested_program():
    mesh = _mesh("data")

    def body(xs):
        def it(c, t):
            def deep(v):
                return jax.jit(
                    lambda u: jax.lax.psum(jnp.sin(u), "data"))(v)

            y = jax.lax.cond(t.sum() > 0, deep, deep, t)
            return c + y.sum(), ()

        c, _ = jax.lax.scan(it, jnp.float32(0), xs)
        return c

    f = shard_map(body, mesh=mesh, in_specs=P(None, "data"), out_specs=P(),
                  check_vma=False)
    return f, (jnp.zeros((4, 64), jnp.float32),)


def test_deep_nesting_inventory_finds_collective():
    fn, args = _deeply_nested_program()
    inv = _inventory(fn, *args)
    psums = [r for r in inv if r.name == "psum"]
    # one per cond branch (each counted once; the scan body once)
    assert psums and all(r.axes == ("data",) for r in psums)
    assert all(r.cond_depth >= 1 and r.loop_depth >= 1 for r in psums)


def test_deep_nesting_contains_prim_unbounded():
    """The old default depth cap (4) stopped exactly at shard_map ->
    scan -> cond -> pjit; the lifted default must see the psum."""
    from apex_tpu.analysis.rules import _contains_prim

    fn, args = _deeply_nested_program()
    closed = jax.make_jaxpr(fn)(*args)
    assert _contains_prim(closed.jaxpr, ("psum",))
    # an explicit cap still works as an opt-in bound
    assert not _contains_prim(closed.jaxpr, ("psum",), max_depth=2)


def test_deep_nesting_budget_enforced():
    fn, args = _deeply_nested_program()
    rep = audit_step(fn, *args,
                     collective_budget=CollectiveBudget(counts={}))
    assert "over_budget_collective" in _codes(rep.findings, "error")


# ---------------------------------------------------------------------------
# the pinned budgets, machine-derived: serving TP + DDP
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tp_engine():
    from apex_tpu.serving import ServingEngine
    from apex_tpu.transformer.testing import GPTConfig, init_gpt_params

    cfg = GPTConfig(
        num_layers=2, num_attention_heads=4, hidden_size=64,
        vocab_size=128, max_position_embeddings=64,
        hidden_dropout=0.0, attention_dropout=0.0,
        compute_dtype=jnp.float32,
    )
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    return ServingEngine(cfg, params, n_slots=2, tp=2, use_kernel=False,
                        prefill_chunk=16, spec_k=2, telemetry_every=4)


def test_serving_psum_pins_are_comm_volume_derived(tp_engine):
    """The PR-16 3-psum pin, now stated per program by the walker: the
    textual str(jaxpr).count is gone and the counts come from
    program_comm_volume."""
    vol = tp_engine.program_comm_volume()
    assert set(vol) == {"decode", "chunk_prefill", "spec_verify"}
    for prog, v in vol.items():
        assert v["psum"]["count"] == 3, (prog, v)
        assert v["psum"]["axes"] == ["tensor"], (prog, v)
        # every collective in every program rides the tensor axis only
        assert all(c["axes"] == ["tensor"] for c in v.values()), (prog, v)
    assert tp_engine.program_psum_counts() == {
        "decode": 3, "chunk_prefill": 3, "spec_verify": 3}


def test_serving_comm_budget_target_green(tp_engine):
    fn, args = tp_engine.step_program()
    budget = CollectiveBudget(
        counts={"psum": 3, "all_gather": 2, "pmax": 1, "pmin": 1},
        axes=("tensor",), max_gather_bytes=1 << 20)
    inv = _inventory(fn, *args)
    assert check_collective_budget(inv, budget) == []


def test_ddp_psum_budget_is_n_buckets_plus_loss(tp_engine):
    """psum count == n_buckets + 1 (the pmean'd loss lowers to psum +
    divide), all over 'data' — the PR-14 pin, derived statically."""
    fn, args, _ = static_audit.build_ddp_step()
    buckets = GradBuckets(args[0], bucket_cap_mb=0.5)
    vol = comm_volume(fn, *args)
    assert buckets.n_buckets >= 2  # the config actually buckets
    assert vol["psum"]["count"] == buckets.n_buckets + 1
    assert vol["psum"]["axes"] == ["data"]
    assert set(vol) == {"psum"}  # no other collective family at all


def test_ddp_collective_budget_helper():
    fn, args, _ = static_audit.build_ddp_step()
    buckets = GradBuckets(args[0], bucket_cap_mb=0.5)
    ddp = DistributedDataParallel(axis_name="data",
                                  gradient_average=False,
                                  bucket_cap_mb=0.5)
    budget = ddp.collective_budget(buckets, extra_psums=1)
    assert budget.counts == {"psum": buckets.n_buckets + 1}
    assert budget.axes == ("data",)
    assert check_collective_budget(_inventory(fn, *args), budget) == []


def test_self_audit_comm_targets_clean():
    """The budget-checked CLI targets (tp_serving_comm / ddp_comm) pass
    with their declared budgets — tier-1 wiring for the comm gates."""
    for target in ("tp_serving_comm", "ddp_comm"):
        fn, args, kw = static_audit.TARGETS[target]()
        assert kw.get("collective_budget") is not None
        rep = assert_step_clean(fn, *args, name=target, **kw)
        assert rep.ok
