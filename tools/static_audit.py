"""Static step auditor CLI — trace the repo's own hot paths, gate on findings.

``apex_tpu.analysis`` audits a traced step (jaxpr walk, no execution);
this tool self-hosts it on the steps the performance story depends on:

- ``gpt_step``         the headline bench configuration in miniature
                       (bf16 GPT + packed FusedAdam, donated carry);
- ``fused_block_step``  the PR-9 headline configuration: the same step
                       with the fused transformer-block tail kernels
                       (``ops/fused_block.py``) and the
                       ``selective_elementwise`` remat policy;
- ``packed_adam_step``  the packed FusedAdam sweep (flat fp32 state,
                       masters, in-place Pallas kernels);
- ``packed_lamb_step``  the packed FusedLAMB two-stage step;
- ``ddp_step``         the bucketed flat-buffer gradient lifecycle:
                       shard_map GPT step with GradBuckets psum-per-
                       bucket, flat amp unscale + found_inf, and the
                       packed FusedAdam fed the reduced buffer directly;
- ``tp_step``          the tensor-parallel serving decode step: a
                       ``ServingEngine(tp=2)`` program shard_mapped
                       over the ``(tensor,)`` submesh (head-sharded
                       paged pool, Megatron GEMM sharding,
                       vocab-parallel sampler), donation and callback
                       gating intact through the wrapper;
- ``telemetry_drain``  the in-jit metrics accumulate + cond-gated async
                       drain path;
- ``tp_serving_comm``  the tp_step program again, audited against its
                       declared ``CollectiveBudget`` (the 3-psum pin,
                       the closed ``tensor`` axis set, and a per-gather
                       byte cap — the "no pool-scale gather" invariant,
                       machine-checked);
- ``ddp_comm``         the ddp_step program audited against the
                       bucketed-sync budget: exactly ``n_buckets``
                       psums for gradients plus one for the pmean'd
                       loss, all over the ``data`` axis.

Usage::

    python tools/static_audit.py --self              # table, exit 1 on errors
    python tools/static_audit.py --self --json       # machine-readable
    python tools/static_audit.py --self --target gpt_step
    python tools/static_audit.py --self --fail-on warning

Exit codes (CI contract, like ``tools/health_report.py``): 0 = clean at
the gated severity, 1 = findings at/above it, 2 = infra/usage error. The
JSON output is deterministic (sorted findings, no timestamps) so a
golden-fixture test pins it (``tests/test_static_audit.py``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# script-mode invocation (`python tools/static_audit.py ...`) puts tools/
# at sys.path[0]; the repo root must be importable for apex_tpu
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# self-audit targets: (fn, args, audit kwargs) builders. Tracing only —
# tiny configs keep a full CPU run in seconds; the invariants checked
# (donation, gating, aliasing, alignment) are size-independent.
# ---------------------------------------------------------------------------
def build_gpt_step():
    """The shape of ``gpt2-345m.train-1chip``'s step: bf16 GPT, packed
    FusedAdam with masters, params+state donated, loss carried."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer.testing import (
        GPTConfig, gpt_loss, init_gpt_params,
    )

    cfg = GPTConfig(
        num_layers=2, num_attention_heads=4, hidden_size=128,
        vocab_size=512, max_position_embeddings=128,
        hidden_dropout=0.0, attention_dropout=0.0,
        compute_dtype=jnp.bfloat16, layer_unroll=-1,
    )
    params = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16),
        init_gpt_params(cfg, jax.random.PRNGKey(0)))
    opt = FusedAdam(lr=1e-4, master_weights=True, packed=True,
                    packed_interpret=True)
    opt_state = opt.init(params)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 128), 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)

    def train_step(params, opt_state, loss_prev):
        loss, grads = jax.value_and_grad(
            lambda p: gpt_loss(cfg, p, tokens, labels))(params)
        params, opt_state = opt.step(grads, opt_state, params)
        return params, opt_state, loss

    step = jax.jit(train_step, donate_argnums=(0, 1))
    return step, (params, opt_state, jnp.float32(0)), {}


def build_fused_block_step():
    """gpt_step with the fused-block tail kernels + selective_elementwise
    remat — the PR-9 headline shape. The kernels run interpreted so the
    REAL pallas calls (and their named scopes / dtype flow) are in the
    traced jaxpr on a CPU host, not the XLA fallback."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer.testing import (
        GPTConfig, gpt_loss, init_gpt_params,
    )

    cfg = GPTConfig(
        num_layers=2, num_attention_heads=4, hidden_size=128,
        vocab_size=512, max_position_embeddings=128,
        hidden_dropout=0.0, attention_dropout=0.0,
        compute_dtype=jnp.bfloat16, layer_unroll=-1,
        fused_block=True, fused_block_interpret=True,
        recompute_granularity="selective_elementwise",
    )
    params = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16),
        init_gpt_params(cfg, jax.random.PRNGKey(0)))
    opt = FusedAdam(lr=1e-4, master_weights=True, packed=True,
                    packed_interpret=True)
    opt_state = opt.init(params)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 128), 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)

    def train_step(params, opt_state, loss_prev):
        loss, grads = jax.value_and_grad(
            lambda p: gpt_loss(cfg, p, tokens, labels))(params)
        params, opt_state = opt.step(grads, opt_state, params)
        return params, opt_state, loss

    step = jax.jit(train_step, donate_argnums=(0, 1))
    return step, (params, opt_state, jnp.float32(0)), {}


def _packed_opt_target(opt_cls, **opt_kw):
    import jax
    import jax.numpy as jnp

    params = {f"w{i}": jnp.zeros((4096,), jnp.bfloat16) for i in range(4)}
    grads = {k: jnp.full((4096,), 1e-3, jnp.bfloat16) for k in params}
    opt = opt_cls(packed=True, packed_interpret=True,
                  packed_chunk_size=4096, master_weights=True, **opt_kw)
    state = opt.init(params)
    step = jax.jit(lambda g, s, p: opt.step(g, s, p), donate_argnums=(1, 2))
    return step, (grads, state, params), {"min_bytes": 4096}


def build_packed_adam_step():
    """The packed FusedAdam sweep: flat fp32 m/v/masters stepped by the
    in-place chunked kernel (ops/packed_optimizer.packed_adam_apply)."""
    from apex_tpu.optimizers import FusedAdam

    return _packed_opt_target(FusedAdam, lr=1e-3)


def build_packed_lamb_step():
    """The packed FusedLAMB two-stage step (stage1 + per-tensor trust
    ratios via segment_sum + scale_update)."""
    from apex_tpu.optimizers import FusedLAMB

    return _packed_opt_target(FusedLAMB, lr=1e-3)


def build_ddp_step():
    """The bucketed flat-buffer gradient lifecycle (ISSUE-14), fused
    spelling: bf16 GPT under shard_map on a 'data' mesh, grads
    bucket-reduced RAW (GradBuckets / one psum per bucket,
    gradient_average deferred), read-only ``found_inf_flat`` off the
    bucket buffers, and ONE ``step_flat`` update sweep — the bucket
    concat arrives lazily (BucketBuffers), unscale + average ride
    ``grad_scale`` into the kernel's inv_scale, overflow skip is the
    kernels' in-sweep noop flag, and next-step params are master-buffer
    views. params+state+scaler donated. The invariants gated: bucket
    buffers donated through to the aliased kernels (no
    double-donation), ONE fp32 upcast for the whole lifecycle (no
    double_cast round-trips), no ungated callbacks, and the bucketed
    PackSpec's layout legality (chunk-aligned bucket bounds)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu.amp import LossScaler
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import DistributedDataParallel, GradBuckets
    from apex_tpu.transformer.testing import (
        GPTConfig, gpt_loss, init_gpt_params,
    )

    cfg = GPTConfig(
        num_layers=2, num_attention_heads=4, hidden_size=128,
        vocab_size=512, max_position_embeddings=128,
        hidden_dropout=0.0, attention_dropout=0.0,
        compute_dtype=jnp.bfloat16, layer_unroll=-1,
    )
    params = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16),
        init_gpt_params(cfg, jax.random.PRNGKey(0)))
    buckets = GradBuckets(params, bucket_cap_mb=0.5)
    opt = FusedAdam(lr=1e-4, master_weights=True, packed=True,
                    packed_interpret=True, packed_spec=buckets.spec)
    opt_state = opt.init(params)
    # gradient_average=False: the /world is deferred into grad_scale
    # (the fused lifecycle's one multiply)
    ddp = DistributedDataParallel(axis_name="data",
                                  gradient_average=False,
                                  bucket_cap_mb=0.5)
    world = len(jax.devices())
    scaler = LossScaler(loss_scale="dynamic", init_scale=2.0 ** 4)
    sstate = scaler.init_state()
    # batch divisible by any world size the audit runs under (1 device
    # standalone, 8 under the pytest harness)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (8, 128), 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)
    mesh = Mesh(np.array(jax.devices()), ("data",))

    def shard_step(params, opt_state, sstate, tokens, labels):
        def scaled_loss(p):
            loss = gpt_loss(cfg, p, tokens, labels)
            return scaler.scale_loss(sstate, loss.astype(jnp.float32))

        _, grads = jax.value_and_grad(scaled_loss)(params)
        bufs, _ = ddp.reduce_flat(grads, buckets=buckets, concat=False)
        new_sstate = scaler.found_inf_flat(sstate, bufs)
        new_opt_state = opt.step_flat(
            bufs, opt_state, found_inf=new_sstate.found_inf,
            grad_scale=new_sstate.loss_scale * world)
        params = buckets.unpack(new_opt_state.master_params)
        opt_state = new_opt_state
        new_sstate = scaler.update_scale(new_sstate)
        loss = jax.lax.pmean(
            gpt_loss(cfg, params, tokens, labels).astype(jnp.float32),
            "data")
        return params, opt_state, new_sstate, loss

    wrapped = shard_map(
        shard_step, mesh=mesh,
        in_specs=(P(), P(), P(), P("data"), P("data")),
        out_specs=(P(), P(), P(), P()),
        check_vma=False)
    step = jax.jit(lambda p, s, ss: wrapped(p, s, ss, tokens, labels),
                   donate_argnums=(0, 1, 2))
    return step, (params, opt_state, sstate), {}


def build_tp_step():
    """The tensor-parallel serving decode step (ISSUE-16): a
    ``ServingEngine(tp=N)`` 1-token program — shard_mapped over the
    ``(tensor,)`` submesh with the head-sharded paged pool, Megatron
    column/row GEMM sharding and the vocab-parallel sampler. tp=2 when
    the host exposes >= 2 devices (the pytest harness forces 8 virtual
    CPU devices), else the tp=1 program (identical code path, no
    collectives). Gated invariants: KV/slot/metrics still donated
    through the shard_map wrapper, telemetry callback still cond-gated
    (and OUTSIDE the shard_map), pool PackSpec chunk-aligned per
    shard."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.serving import ServingEngine
    from apex_tpu.transformer.testing import GPTConfig, init_gpt_params

    tp = 2 if len(jax.devices()) >= 2 else 1
    cfg = GPTConfig(
        num_layers=2, num_attention_heads=4, hidden_size=64,
        vocab_size=128, max_position_embeddings=64,
        hidden_dropout=0.0, attention_dropout=0.0,
        compute_dtype=jnp.float32,
    )
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, n_slots=2, tp=tp,
                        use_kernel=False, telemetry_every=4)
    fn, args = eng.step_program()
    return fn, args, {"pack_specs": [eng.spec.pack_spec],
                      "shard_count": eng.tp}


def build_telemetry_drain():
    """The sync-free metrics path: on-device accumulate + the async
    drain that must stay behind lax.cond (telemetry/metrics.py)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import telemetry

    sink = telemetry.NullRecorder()

    def step(metrics, loss):
        metrics = telemetry.accumulate(metrics, loss=loss, tokens=256)
        metrics = telemetry.drain(metrics, sink, every_n=10)
        return metrics, loss * jnp.float32(0.5)

    jitted = jax.jit(step, donate_argnums=(0,))
    return jitted, (telemetry.init_metrics(), jnp.float32(0)), {}


def build_tp_serving_comm():
    """The tp_step program under its declared communication contract
    (ISSUE-19): the decode program may contain exactly 3 psums (attn
    row-GEMM tail, MLP row-GEMM tail, vocab-parallel sampler), 2
    all_gathers and one pmax/pmin pair (the sampler's cross-shard
    argmax plumbing), all over the ``tensor`` axis only, and no single
    gather may materialize >= 1 MiB (the pool-scale-gather ban from
    ISSUE-16, previously only a grep over the jaxpr text). At tp=1 the
    same program must contain NO collectives at all."""
    fn, args, kw = build_tp_step()

    from apex_tpu.analysis import CollectiveBudget

    if (kw.get("shard_count") or 1) > 1:
        budget = CollectiveBudget(
            counts={"psum": 3, "all_gather": 2, "pmax": 1, "pmin": 1},
            axes=("tensor",), max_gather_bytes=1 << 20)
    else:
        budget = CollectiveBudget(counts={}, axes=())
    return fn, args, dict(kw, collective_budget=budget)


def build_ddp_comm():
    """The ddp_step program under the bucketed gradient-sync budget:
    exactly ``n_buckets`` psums for the flat gradient buffers plus one
    for the pmean'd loss (pmean lowers to psum + divide), every one of
    them over the ``data`` axis — the machine form of the PR-14
    psum-count==n_buckets jaxpr pin."""
    fn, args, kw = build_ddp_step()

    from apex_tpu.parallel import DistributedDataParallel, GradBuckets

    buckets = GradBuckets(args[0], bucket_cap_mb=0.5)
    ddp = DistributedDataParallel(axis_name="data",
                                  gradient_average=False,
                                  bucket_cap_mb=0.5)
    # +1: the pmean'd loss rides the same axis outside the buckets
    budget = ddp.collective_budget(buckets, extra_psums=1)
    return fn, args, dict(kw, collective_budget=budget)


TARGETS = {
    "gpt_step": build_gpt_step,
    "fused_block_step": build_fused_block_step,
    "packed_adam_step": build_packed_adam_step,
    "packed_lamb_step": build_packed_lamb_step,
    "ddp_step": build_ddp_step,
    "tp_step": build_tp_step,
    "telemetry_drain": build_telemetry_drain,
    "tp_serving_comm": build_tp_serving_comm,
    "ddp_comm": build_ddp_comm,
}


def run_self_audit(targets=None, rules=None):
    """Audit every (selected) self-target; returns the stable result dict."""
    from apex_tpu import analysis

    names = list(targets) if targets else sorted(TARGETS)
    out = {"event": "static_audit", "targets": {}}
    ok = True
    for name in names:
        fn, args, kw = TARGETS[name]()
        if rules:
            kw = dict(kw, rules=rules)
        report = analysis.audit_step(fn, *args, name=name, **kw)
        out["targets"][name] = report.to_dict()
        ok = ok and report.ok
    out["ok"] = ok
    return out


def summarize(result: dict) -> dict:
    """The one-line summary the CLI prints: counts per severity plus
    the distinct finding codes (stable, sorted)."""
    counts = {"error": 0, "warning": 0, "info": 0}
    codes = set()
    for t in result["targets"].values():
        for sev, n in t["counts"].items():
            counts[sev] += n
        codes.update(f["code"] for f in t["findings"])
    return {"ok": result["ok"], **counts, "codes": sorted(codes)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Static jaxpr audit of apex_tpu's own training steps")
    ap.add_argument("--self", action="store_true", dest="self_audit",
                    help="audit the repo's headline steps (required mode)")
    ap.add_argument("--target", action="append", choices=sorted(TARGETS),
                    help="restrict to specific target(s)")
    ap.add_argument("--rules", help="comma-separated rule subset "
                                    "(default: all)")
    ap.add_argument("--json", action="store_true",
                    help="emit the full result as JSON")
    ap.add_argument("--fail-on", choices=["error", "warning"],
                    default="error",
                    help="exit non-zero at this severity (default error)")
    args = ap.parse_args(argv)
    if not args.self_audit:
        ap.error("nothing to do: pass --self (audit the repo's own steps)")

    rules = tuple(r for r in (args.rules or "").split(",") if r) or None
    try:
        result = run_self_audit(targets=args.target, rules=rules)
    except Exception as e:  # infra failure must not read as "clean"
        print(f"static audit failed: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2

    if args.json:
        print(json.dumps(result, indent=2))
    else:
        from apex_tpu.analysis import AuditReport, Finding

        for name, t in result["targets"].items():
            rep = AuditReport(name, [
                Finding(f["rule"], f["code"], f["severity"], f["message"],
                        f.get("where", ""), f.get("data"))
                for f in t["findings"]], tuple(t["rules_run"]))
            print(rep.table())
            print()
        print("summary:", json.dumps(summarize(result)))

    gate = {"error": ("error",), "warning": ("error", "warning")}[args.fail_on]
    bad = sum(t["counts"][s] for t in result["targets"].values()
              for s in gate)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
