"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one command, no arguments::

    python chip_smoke.py

It drives the two main paths once, through the entry points a user calls,
at the full width of GPT-2 345M (24 layers x hidden 1024 x 16 heads of 64,
vocab 50304; random weights from a seed):

- **trainer** — ``amp.initialize(O2)`` -> ``amp.scaled_value_and_grad`` ->
  ``FusedAdam(packed=True).step(found_inf=)`` -> ``scaler.update_scale`` on
  the step of ``gpt2-345m.train-1chip`` (flash attention, the
  ``fused_block`` tails with ``selective_elementwise`` recompute, which on
  the chip lower to XLA's own fusions, chunk-fused LM-head CE),
  batch 8 x seq 1024: the loss falls over a few chained steps, and a step
  with an injected overflow leaves params untouched and halves the scale;
- **flat scaler** — ``LossScaler.unscale_flat`` / ``found_inf_flat`` on a
  345M-element flat gradient buffer, clean and with an inf planted, against
  their ``use_kernel=False`` path (the bucketed lifecycle's sweeps, which
  the pytree amp route above does not reach);
- **server** — ``ServingEngine(n_slots=8, prefill_chunk=8)`` with and without
  ``spec_k=2`` on the same weights, so ``generate()`` runs all three programs
  (decode, chunk-prefill, spec-verify) over staggered requests: every request
  COMPLETED, invariants clean, no page leaked, every emitted token within a
  stated tolerance of the dense forward's argmax, and the compiled
  ``flash_decode`` against ``_decode_xla`` on the engine's own pool;
- **four chips** (when jax sees >= 4) — the same train step data-parallel
  through ``GradBuckets`` + ``DistributedDataParallel`` + ``step_flat``, and
  ``ServingEngine(tp=4)`` on the same requests.

Each leg also proves from the traced programs (``analysis.kernel_inventory``)
that the Pallas kernels it expects are present and were handed to the
compiler, not the interpreter — model code picks XLA paths silently when a
gate says no.

Anything but a TPU backend is an immediate non-zero exit; no switch lets the
script pass off-chip. Any exception, non-finite value or failed check fails
the run. The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``; the full record
goes to ``chiprun_out/chip_smoke.json``.

The legs are plain functions of a :class:`Size` so tier-1 drives them at toy
width on the CPU mesh with interpreted kernels (``tests/test_chip_smoke.py``);
``__main__`` always uses the full width.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# The dense forward and the paged engine compute the same logits in bf16
# through different kernels and summation orders, so on random weights
# (logit spread ~0.6, top-two gap ~0.1) greedy argmax is not bit-stable
# between them. A token passes when the dense forward scores it within
# this much of its own best token: bf16 rounding (2^-8 relative) compounded
# over 24 layers, with margin; a wrong token sits ~3 below the maximum.
LOGIT_TOLERANCE = 0.1
# flash_decode (compiled) against _decode_xla, bf16 outputs of O(1) values
DECODE_TOLERANCE = 2e-2


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


@dataclasses.dataclass(frozen=True)
class Size:
    """What the legs are functions of. ``interpret`` runs every Pallas
    kernel under the interpreter and forces the kernel paths on (the CPU
    configuration); on the chip nothing is forced — the legs take the
    paths a user gets and assert they were the kernels."""

    layers: int = 24
    hidden: int = 1024
    heads: int = 16
    vocab: int = 50304
    seq: int = 1024            # training sequence, serving positions
    batch: int = 8             # per chip
    train_steps: int = 5
    n_slots: int = 8
    n_requests: int = 8
    prompt_len: int = 128
    new_tokens: int = 32
    bucket_cap_mb: float = 25.0
    interpret: bool = False

    def gpt_config(self, **kw):
        from apex_tpu.transformer.testing import GPTConfig

        return GPTConfig(
            num_layers=self.layers, num_attention_heads=self.heads,
            hidden_size=self.hidden, vocab_size=self.vocab,
            max_position_embeddings=self.seq,
            hidden_dropout=0.0, attention_dropout=0.0,
            compute_dtype=jnp.bfloat16, **kw)

    def train_config(self):
        """The step of ``gpt2-345m.train-1chip``."""
        return self.gpt_config(
            recompute_granularity="selective_elementwise",
            layer_unroll=-1, fused_block=True,
            fused_block_interpret=self.interpret,
            use_flash_attention=True if self.interpret else None)


FULL = Size()


class Smoke:
    """The run's record: timed sections (compile seconds apart from wall
    seconds, from the package's compile ledger), checks, and notes."""

    def __init__(self, out=None):
        from apex_tpu.telemetry import compiles

        self.out = out if out is not None else sys.stdout
        self.record: Dict[str, Any] = {"sections": {}}
        self._totals = compiles.totals

    def close(self) -> None:
        """Nothing to release (the listeners are the package's ledger's);
        the run's callers and ``tests/test_chip_smoke.py`` end with it."""
        self.out.flush()

    def say(self, msg: str) -> None:
        print(msg, file=self.out, flush=True)

    def check(self, ok: bool, what: str) -> None:
        if not ok:
            raise SmokeFailure(what)
        self.say(f"  ok: {what}")

    @contextlib.contextmanager
    def section(self, name: str):
        """Time a block; ``compile_s`` is what jax spent in the backend
        compiler (or fetching from the persistent cache) inside it."""
        before, t0 = self._totals(), time.perf_counter()
        rec: Dict[str, Any] = {}
        yield rec
        after = self._totals()
        rec["wall_s"] = round(time.perf_counter() - t0, 3)
        rec["compile_s"] = round(after["compile_s"] - before["compile_s"], 3)
        rec["cache_hits"] = after["hits"] - before["hits"]
        self.record["sections"][name] = rec
        self.say(f"[{name}] " + json.dumps(rec))


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------
def _device_ids(tree) -> List[int]:
    """Sorted ids of every device that holds a shard of any leaf."""
    ids = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            ids |= {d.id for d in leaf.sharding.device_set}
    return sorted(ids)


# Per-leaf fp32 sums: equal before and after a skipped step exactly when
# nothing was written (the sums are deterministic).
_fingerprint = jax.jit(lambda tree: jax.tree_util.tree_map(
    lambda x: jnp.sum(x.astype(jnp.float32)), tree))


def _same(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def _all_finite(tree) -> bool:
    return all(bool(np.all(np.isfinite(np.asarray(x))))
               for x in jax.tree_util.tree_leaves(tree))


def require_kernels(smoke: Smoke, program: str, inventory,
                    names: Sequence[str], *, interpret: bool,
                    absent: Sequence[str] = ()) -> None:
    """Every name in ``names`` must be a ``pallas_call`` of the traced
    program, and none of them may run under the interpreter unless the
    size asked for it; a name in ``absent`` (a kernel whose gate says it
    does not engage here) must not be there at all."""
    seen = {}
    for rec in inventory:
        seen.setdefault(rec.name, []).append(rec.compiled)
    unexpected = sorted(set(absent) & set(seen))
    if unexpected:
        raise SmokeFailure(
            f"{program}: kernels in the traced program whose gate says "
            f"they do not engage here: {unexpected}")
    missing = sorted(set(names) - set(seen))
    if missing:
        raise SmokeFailure(
            f"{program}: kernels missing from the traced program (an XLA "
            f"path was taken instead): {missing}; present: {sorted(seen)}")
    wrong = sorted(n for n in names
                   if any(c == interpret for c in seen[n]))
    if wrong:
        raise SmokeFailure(
            f"{program}: kernels "
            f"{'compiled' if interpret else 'interpreted'} but expected "
            f"{'interpreted' if interpret else 'compiled'}: {wrong}")
    smoke.record.setdefault("kernels", {})[program] = {
        n: len(seen[n]) for n in sorted(names)}
    smoke.say(f"  ok: {program}: "
              f"{'interpreted' if interpret else 'compiled'} pallas_calls "
              + ", ".join(f"{n} x{len(seen[n])}" for n in sorted(names)))


# the block-tail kernels of ops/fused_block.py: in the traced step where
# their gate engages them (interpreted at toy width; on a chip the gate
# hands the tails to XLA's own fusions, so there they must be absent)
TAIL_KERNELS = (
    "apex_tpu_bias_gelu_fwd", "apex_tpu_bias_gelu_bwd",
    "apex_tpu_bias_dropout_residual_fwd",
    "apex_tpu_bias_dropout_residual_bwd",
    "apex_tpu_residual_ln_fwd", "apex_tpu_residual_ln_bwd",
)
CHIP_TRAIN_KERNELS = (
    "apex_tpu_flash_fwd", "apex_tpu_flash_bwd_dkv", "apex_tpu_packed_adam",
)
TRAIN_KERNELS = CHIP_TRAIN_KERNELS + TAIL_KERNELS


def train_kernels(size: Size) -> Dict[str, Tuple[str, ...]]:
    """``require_kernels``' ``names`` and ``absent`` for ``train_config``'s
    step at this size: the tails are expected where
    ``fused_block_available`` says their gate engages, else forbidden."""
    from apex_tpu.ops import fused_block_available

    if size.interpret or fused_block_available(size.hidden):
        return {"names": TRAIN_KERNELS, "absent": ()}
    return {"names": CHIP_TRAIN_KERNELS, "absent": TAIL_KERNELS}


def _batch(size: Size, n_rows: int):
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (n_rows, size.seq), 0, size.vocab)
    return tokens, jnp.roll(tokens, -1, axis=1)


def _init_params(cfg):
    """``init_gpt_params`` as ONE compiled program: run eagerly, its
    per-leaf random/cast ops each compile on their own — 80 s of a cold
    start on the chip."""
    from apex_tpu.transformer.testing import init_gpt_params

    return jax.jit(lambda key: init_gpt_params(cfg, key))(
        jax.random.PRNGKey(0))


def _memory_analysis(compiled) -> Dict[str, int]:
    """The compiler's own account of a program's device memory."""
    ma = compiled.memory_analysis()
    return {"argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes}


def _peak_bytes() -> Optional[int]:
    stats = jax.devices()[0].memory_stats()
    return None if not stats else int(stats.get("peak_bytes_in_use", 0))


# ---------------------------------------------------------------------------
# trainer (one chip)
# ---------------------------------------------------------------------------
def train_program(size: Size):
    """``(jitted step, (params, opt_state, sstate))`` — the amp O2 flow
    around ``train_config``'s step. The step's last argument scales
    the loss: 1.0, or inf for the injected-overflow step, so one
    compiled program serves both."""
    from apex_tpu import amp
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer.testing import gpt_loss

    cfg = size.train_config()
    params = _init_params(cfg)
    opt = FusedAdam(lr=1e-4, packed=True, packed_interpret=size.interpret)
    params, opt, amp_state = amp.initialize(params, opt, opt_level="O2")
    scaler = amp_state.scaler(0)
    tokens, labels = _batch(size, size.batch)
    grad_fn = amp.scaled_value_and_grad(
        lambda p, overflow: gpt_loss(cfg, p, tokens, labels) * overflow,
        scaler)

    def train_step(params, opt_state, sstate, overflow):
        loss, grads, sstate = grad_fn(sstate, params, overflow)
        params, opt_state = opt.step(grads, opt_state, params,
                                     found_inf=sstate.found_inf)
        return params, opt_state, scaler.update_scale(sstate), loss

    return (jax.jit(train_step, donate_argnums=(0, 1, 2)),
            (params, jax.jit(opt.init)(params), amp_state.scaler_state(0)))


def train_leg(smoke: Smoke, size: Size) -> None:
    from apex_tpu.analysis import kernel_inventory

    smoke.say(f"== trainer: {size.layers} layers, batch {size.batch} x "
              f"seq {size.seq}")
    with smoke.section("train_setup"):
        step, (params, opt_state, sstate) = train_program(size)
    smoke.check(opt_state.master_params.dtype == jnp.float32 and all(
        p.dtype == jnp.bfloat16 for p in jax.tree_util.tree_leaves(params)),
        "amp O2: bf16 params, fp32 masters in the optimizer")
    one, inf = jnp.float32(1.0), jnp.float32(np.inf)
    with smoke.section("train_trace"):
        traced = step.trace(params, opt_state, sstate, one)
        require_kernels(smoke, "train_step", kernel_inventory(traced.jaxpr),
                        interpret=size.interpret, **train_kernels(size))
    with smoke.section("train_compile") as rec:
        compiled = traced.lower().compile()
        rec.update(_memory_analysis(compiled))

    scale0 = float(sstate.loss_scale)
    losses = []
    with smoke.section("train_steps") as rec:
        for _ in range(size.train_steps):
            params, opt_state, sstate, loss = compiled(
                params, opt_state, sstate, one)
            losses.append(loss)
        losses = [float(x) for x in losses]  # the host read ends the chain
        rec["steps"] = size.train_steps
        rec["losses"] = [round(x, 4) for x in losses]
    smoke.check(all(np.isfinite(losses)), f"losses finite: {losses}")
    smoke.check(losses[-1] < losses[0],
                f"loss fell: {losses[0]:.4f} -> {losses[-1]:.4f}")
    smoke.check(float(sstate.loss_scale) == scale0,
                f"loss scale held at {scale0:g} over clean steps")

    with smoke.section("train_overflow_step"):
        before = _fingerprint((params, opt_state.master_params,
                               opt_state.exp_avg))
        step_before = int(opt_state.step)
        params, opt_state, sstate, loss = compiled(
            params, opt_state, sstate, inf)
        after = _fingerprint((params, opt_state.master_params,
                              opt_state.exp_avg))
    smoke.check(not np.isfinite(float(loss)),
                "injected overflow reached the loss")
    smoke.check(_same(before, after) and _all_finite(after)
                and int(opt_state.step) == step_before,
                "overflow step skipped: params, masters, moments and step "
                "count unchanged and finite")
    smoke.check(float(sstate.loss_scale) == scale0 / 2,
                f"loss scale halved: {scale0:g} -> "
                f"{float(sstate.loss_scale):g}")
    smoke.record["train"] = {
        "losses": losses, "peak_bytes_in_use": _peak_bytes(),
        "params_on": _device_ids(params),
        "opt_state_on": _device_ids(opt_state)}
    smoke.say(f"  peak device memory after the train leg: "
              f"{smoke.record['train']['peak_bytes_in_use']}")


# ---------------------------------------------------------------------------
# the scaler's flat sweeps (the bucketed lifecycle's, run alone)
# ---------------------------------------------------------------------------
def flat_scaler_leg(smoke: Smoke, n_elems: int, *, interpret: bool) -> None:
    from apex_tpu.amp import LossScaler
    from apex_tpu.analysis import kernel_inventory

    smoke.say(f"== flat scaler sweeps over {n_elems} elements")
    scaler = LossScaler(loss_scale="dynamic", init_scale=2.0 ** 10)
    sstate = scaler.init_state()
    clean = (jax.random.normal(jax.random.PRNGKey(3), (n_elems,),
                               jnp.float32) * 8.0).astype(jnp.bfloat16)
    # a ragged index on purpose: not a row or chunk boundary
    planted = clean.at[n_elems // 3 + 5].set(jnp.inf)

    def unscale(flat, use_kernel):
        return scaler.unscale_flat(
            sstate, flat, out_dtype=jnp.float32,
            use_kernel=use_kernel, interpret=interpret and use_kernel)

    kernel = jax.jit(lambda f: unscale(f, True))
    with smoke.section("flat_scaler_trace"):
        traced = kernel.trace(clean)
        require_kernels(smoke, "unscale_flat", kernel_inventory(traced.jaxpr),
                        ("apex_tpu_multi_tensor_scale_flat",),
                        interpret=interpret)
    with smoke.section("flat_scaler_compile"):
        compiled = traced.lower().compile()
    xla = jax.jit(lambda f: unscale(f, False))
    probe = jax.jit(lambda f: scaler.found_inf_flat(sstate, f).found_inf)
    with smoke.section("flat_scaler_run"):
        for name, flat, want in (("clean", clean, False),
                                 ("planted inf", planted, True)):
            out_k, st_k = compiled(flat)
            out_x, st_x = xla(flat)
            smoke.check(bool(st_k.found_inf) == want
                        and bool(st_x.found_inf) == want
                        and bool(probe(flat)) == want,
                        f"{name}: found_inf == {want} from the kernel, the "
                        "XLA path and the found_inf_flat probe")
            smoke.check(bool(jnp.array_equal(out_k, out_x, equal_nan=True)),
                        f"{name}: kernel values equal the XLA path's")


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------
def _requests(size: Size):
    from apex_tpu.serving import Request

    rng = np.random.default_rng(0)
    return [
        Request(prompt=[int(t) for t in
                        rng.integers(0, size.vocab, size=size.prompt_len)],
                max_new_tokens=size.new_tokens,
                arrival_step=4 * i)
        for i in range(size.n_requests)]


def _engine_programs(eng):
    progs = [("decode", eng.step_program)]
    if eng.prefill_chunk > 1:
        progs.append(("chunk_prefill", eng.chunk_step_program))
    if eng.spec_k > 0:
        progs.append(("spec_verify", eng.spec_step_program))
    return progs


def _serve(smoke: Smoke, size: Size, params, name: str, **engine_kw):
    """One engine through ``generate()``; returns ``(tokens by request,
    engine)`` after the lifecycle checks."""
    from apex_tpu.analysis import kernel_inventory
    from apex_tpu.serving import RequestStatus, ServingEngine

    cfg = size.gpt_config()
    with smoke.section(f"{name}_build"):
        eng = ServingEngine(cfg, params, n_slots=size.n_slots,
                            prefill_chunk=8, interpret=size.interpret,
                            **engine_kw)
        for prog, build in _engine_programs(eng):
            fn, args = build()
            require_kernels(smoke, f"{name}/{prog}",
                            kernel_inventory(fn, *args),
                            ("apex_tpu_flash_decode",),
                            interpret=size.interpret)
            del args
    reqs = _requests(size)
    with smoke.section(f"{name}_generate") as rec:
        out = eng.generate(reqs)
        st = eng.last_stats
        rec.update(steps=st["steps"], generated=st["generated_tokens"],
                   preemptions=st["preemptions"])
    smoke.check(all(r.status is RequestStatus.COMPLETED for r in reqs),
                f"{name}: all {len(reqs)} requests COMPLETED")
    smoke.check(all(len(out[r.rid]) == size.new_tokens for r in reqs),
                f"{name}: {size.new_tokens} tokens per request")
    eng.scheduler.check_invariants()
    smoke.check(eng.scheduler.allocator.used_count == 0,
                f"{name}: invariants clean, no page leaked")
    return [out[r.rid] for r in reqs], eng


def _check_against_dense(smoke: Smoke, size: Size, params, name: str,
                         outs: Sequence[Sequence[int]]) -> None:
    """Teacher-forced reference: ONE dense forward (the training model,
    the repo's reference for the engine) over prompt + emitted tokens
    scores every emitted token at its own position."""
    from apex_tpu.amp import cast_params_for_inference
    from apex_tpu.transformer.testing.standalone_transformer_lm import (
        gpt_forward,
    )

    cfg = size.gpt_config()
    reqs = _requests(size)
    full = np.asarray([r.prompt + list(o) for r, o in zip(reqs, outs)],
                      np.int32)
    p_len, n_new = size.prompt_len, size.new_tokens

    def score(p, toks):
        logits = gpt_forward(cfg, p, toks)[:, p_len - 1:p_len - 1 + n_new]
        logits = logits.astype(jnp.float32)
        chosen = jnp.take_along_axis(
            logits, toks[:, p_len:, None], axis=-1)[..., 0]
        return jnp.max(logits, axis=-1) - chosen, jnp.isfinite(logits).all()

    with smoke.section(f"{name}_dense_reference") as rec:
        gap, finite = jax.jit(score)(
            cast_params_for_inference(params, cfg.compute_dtype),
            jnp.asarray(full))
        gap = np.asarray(gap)
        rec["max_gap"] = round(float(gap.max()), 5)
        rec["argmax_identical"] = f"{int((gap == 0).sum())}/{gap.size}"
    smoke.check(bool(finite), f"{name}: reference logits finite")
    smoke.check(float(gap.max()) <= LOGIT_TOLERANCE,
                f"{name}: every emitted token within {LOGIT_TOLERANCE} of "
                f"the dense forward's best logit (max gap "
                f"{gap.max():.5f}; argmax-identical "
                f"{rec['argmax_identical']})")


def _check_decode_on_pool(smoke: Smoke, size: Size, eng, name: str) -> None:
    """The compiled kernel against ``_decode_xla`` on the pool the engine
    just filled (freed pages keep their K/V), ragged lengths, one empty
    slot."""
    from apex_tpu.ops.flash_decode import flash_decode

    spec = eng.spec
    rng = np.random.default_rng(5)
    b = size.n_slots
    q = jnp.asarray(rng.normal(size=(b, spec.num_heads, spec.head_dim)),
                    spec.dtype)
    pt = jnp.asarray(rng.integers(1, spec.num_pages,
                                  size=(b, spec.pages_per_seq)), jnp.int32)
    top = size.prompt_len + size.new_tokens
    lens = jnp.asarray([0] + [int(x) for x in
                              rng.integers(1, top + 1, size=b - 1)],
                       jnp.int32)
    layer = spec.num_layers // 2
    pages = jax.device_get(eng.kv.pages[layer])
    k_pages, v_pages = jnp.asarray(pages[0]), jnp.asarray(pages[1])
    with smoke.section(f"{name}_decode_parity") as rec:
        kern = jax.jit(lambda *a: flash_decode(
            *a, interpret=size.interpret))(q, k_pages, v_pages, pt, lens)
        ref = jax.jit(lambda *a: flash_decode(
            *a, use_kernel=False))(q, k_pages, v_pages, pt, lens)
        err = float(jnp.max(jnp.abs(kern.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        rec["max_abs_err"] = round(err, 6)
    smoke.check(bool(jnp.any(k_pages != 0)), f"{name}: pool holds real K/V")
    smoke.check(err <= DECODE_TOLERANCE and bool(jnp.all(kern[0] == 0)),
                f"{name}: flash_decode kernel within {DECODE_TOLERANCE} of "
                f"_decode_xla on the engine's pool (max |err| {err:.6f}), "
                "empty slot zero")


def serve_leg(smoke: Smoke, size: Size, *, tp: int = 1,
              baseline: Optional[Sequence[Sequence[int]]] = None):
    """Plain and speculative engines over the same requests; returns the
    plain engine's tokens (the four-chip leg compares against them)."""
    tag = f"serve_tp{tp}"
    smoke.say(f"== server (tp={tp}): {size.n_requests} requests, "
              f"{size.prompt_len} prompt + {size.new_tokens} new tokens, "
              f"{size.n_slots} slots")
    with smoke.section(f"{tag}_weights"):
        params = _init_params(size.gpt_config())
    placement = {}
    outs = {}
    for name, kw in ((f"{tag}_plain", {}), (f"{tag}_spec", {"spec_k": 2})):
        outs[name], eng = _serve(smoke, size, params, name, tp=tp, **kw)
        _check_against_dense(smoke, size, params, name, outs[name])
        if not kw:
            _check_decode_on_pool(smoke, size, eng, name)
        placement[name] = {"params_on": _device_ids(eng.params),
                           "kv_pool_on": _device_ids(eng.kv),
                           "page_size": eng.spec.page_size}
        smoke.say(f"  {name}: params on devices "
                  f"{placement[name]['params_on']}, KV pool on "
                  f"{placement[name]['kv_pool_on']}, page size "
                  f"{eng.spec.page_size}")
        del eng
        gc.collect()
    plain, spec = outs[f"{tag}_plain"], outs[f"{tag}_spec"]
    same = sum(a == b for a, b in zip(plain, spec))
    smoke.say(f"  spec_k=2 token-identical to plain greedy on {same}/"
              f"{len(plain)} requests (bf16: not required, the dense "
              "tolerance check is)")
    smoke.record[tag] = {"placement": placement,
                         "spec_identical_requests": same}
    if baseline is not None:
        same = sum(a == b for a, b in zip(plain, baseline))
        smoke.record[tag]["identical_to_one_chip"] = same
        smoke.say(f"  tp={tp} token-identical to the one-chip run on "
                  f"{same}/{len(plain)} requests (bf16 reduction order: "
                  "not required, the dense tolerance check is)")
    return plain


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def dp_train_program(size: Size, devices):
    """``(jitted step, (opt_state, sstate, tokens, labels), ddp,
    buckets)`` — the train step data-parallel over ``devices``: bucketed
    psums, ``found_inf_flat`` probe, one ``step_flat`` sweep
    (docs/distributed.md, the fused spelling). The fp32 master buffer is
    the parameter store; the forward takes bf16 leaf views of it."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from apex_tpu.amp import LossScaler
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import DistributedDataParallel, GradBuckets
    from apex_tpu.transformer.testing import gpt_loss

    n_dev = len(devices)
    cfg = size.train_config()
    mesh = Mesh(np.asarray(devices), ("data",))
    rep = NamedSharding(mesh, P())
    params = jax.device_put(jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16), _init_params(cfg)), rep)
    buckets = GradBuckets(params, bucket_cap_mb=size.bucket_cap_mb)
    opt = FusedAdam(lr=1e-4, master_weights=True, packed=True,
                    packed_interpret=size.interpret,
                    packed_spec=buckets.spec)
    opt_state = jax.device_put(jax.jit(opt.init)(params), rep)
    # gradient_average=False: the 1/world rides grad_scale into the sweep
    ddp = DistributedDataParallel(
        axis_name="data", gradient_average=False,
        bucket_cap_mb=size.bucket_cap_mb)
    scaler = LossScaler(loss_scale="dynamic")
    sstate = jax.device_put(scaler.init_state(), rep)
    tokens, labels = jax.device_put(
        _batch(size, size.batch * n_dev), NamedSharding(mesh, P("data")))

    def shard_step(opt_state, sstate, tokens, labels):
        params = buckets.unpack(opt_state.master_params)

        def scaled_loss(p):
            loss = gpt_loss(cfg, p, tokens, labels).astype(jnp.float32)
            return scaler.scale_loss(sstate, loss), loss

        (_, loss), grads = jax.value_and_grad(
            scaled_loss, has_aux=True)(params)
        bufs, _ = ddp.reduce_flat(grads, buckets=buckets, concat=False)
        sstate = scaler.found_inf_flat(sstate, bufs)
        opt_state = opt.step_flat(
            bufs, opt_state, found_inf=sstate.found_inf,
            grad_scale=sstate.loss_scale * n_dev)
        return (opt_state, scaler.update_scale(sstate),
                jax.lax.pmean(loss, "data"))

    step = jax.jit(
        jax.shard_map(shard_step, mesh=mesh,
                      in_specs=(P(), P(), P("data"), P("data")),
                      out_specs=(P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1))
    return step, (opt_state, sstate, tokens, labels), ddp, buckets


def dp_train_leg(smoke: Smoke, size: Size, n_dev: int) -> None:
    from apex_tpu.analysis import (
        check_collective_budget, collective_inventory, kernel_inventory,
    )

    smoke.say(f"== data-parallel trainer over {n_dev} chips, global batch "
              f"{size.batch * n_dev}")
    with smoke.section("dp_setup"):
        step, (opt_state, sstate, tokens, labels), ddp, buckets = \
            dp_train_program(size, jax.devices()[:n_dev])

    with smoke.section("dp_trace"):
        traced = step.trace(opt_state, sstate, tokens, labels)
        require_kernels(
            smoke, "dp_train_step", kernel_inventory(traced.jaxpr),
            interpret=size.interpret, **train_kernels(size))
        budget = ddp.collective_budget(buckets, extra_psums=1)
        findings = check_collective_budget(
            collective_inventory(traced.jaxpr.jaxpr), budget,
            where="dp_train_step")
    smoke.check(not findings,
                f"psum count equals the bucket budget "
                f"({buckets.n_buckets} buckets + 1 loss pmean)"
                + "".join(f"; {f.message}" for f in findings))
    with smoke.section("dp_compile") as rec:
        compiled = traced.lower().compile()
        rec.update(_memory_analysis(compiled))
    losses = []
    with smoke.section("dp_steps") as rec:
        for _ in range(size.train_steps):
            opt_state, sstate, loss = compiled(
                opt_state, sstate, tokens, labels)
            losses.append(loss)
        losses = [float(x) for x in losses]
        rec["losses"] = [round(x, 4) for x in losses]
    smoke.check(all(np.isfinite(losses)) and losses[-1] < losses[0],
                f"DP losses finite and falling: {losses}")
    on = _device_ids(opt_state)
    smoke.check(len(on) == n_dev,
                f"masters and optimizer state held on all {n_dev} chips: "
                f"{on}")
    smoke.record["dp_train"] = {
        "losses": losses, "n_buckets": buckets.n_buckets,
        "params_and_opt_state_on": on,
        "peak_bytes_in_use": _peak_bytes()}


def four_chip_leg(smoke: Smoke, size: Size,
                  one_chip_tokens: Sequence[Sequence[int]]) -> None:
    dp_train_leg(smoke, size, 4)
    gc.collect()
    serve_leg(smoke, size, tp=4, baseline=one_chip_tokens)
    pool_on = smoke.record["serve_tp4"]["placement"]["serve_tp4_plain"][
        "kv_pool_on"]
    smoke.check(len(pool_on) == 4,
                f"tp=4 KV pool sharded over 4 chips: {pool_on}")


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------
def _versions() -> Dict[str, str]:
    import importlib.metadata as md

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu", "flax"):
        try:
            out[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            out[pkg] = "not installed"
    return out


def main() -> int:
    from apex_tpu.chip import require_tpu, use_compile_cache

    device = require_tpu("chip_smoke.py")  # exits before compiling anything
    t_start = time.perf_counter()
    smoke = Smoke()
    cache_dir = use_compile_cache()
    from apex_tpu.ops import hostio

    smoke.record.update(device=device, versions=_versions(),
                        compile_cache_dir=cache_dir,
                        native_hostio=hostio.native_available())
    smoke.say(json.dumps({k: smoke.record[k] for k in (
        "device", "versions", "compile_cache_dir", "native_hostio")}))
    ok = False
    try:
        train_leg(smoke, FULL)
        gc.collect()
        pack = 1024 * 64  # a chunk multiple near the 345M parameter count
        flat_scaler_leg(smoke, 355_000_000 // pack * pack,
                        interpret=False)
        gc.collect()
        tokens = serve_leg(smoke, FULL)
        if device["count"] >= 4:
            gc.collect()
            four_chip_leg(smoke, FULL, tokens)
        else:
            smoke.record["four_chip_leg"] = (
                f"not run: {device['count']} device(s) visible")
            smoke.say(f"== four-chip leg not run: {device['count']} "
                      "device(s) visible")
        ok = True
    except BaseException as e:
        smoke.record["failure"] = f"{type(e).__name__}: {e}"
        raise
    finally:
        smoke.record["ok"] = ok
        smoke.record["total_wall_s"] = round(
            time.perf_counter() - t_start, 1)
        smoke.record["compile_s_total"] = round(sum(
            s["compile_s"] for s in smoke.record["sections"].values()), 1)
        smoke.record["peak_bytes_in_use"] = _peak_bytes()
        os.makedirs("chiprun_out", exist_ok=True)
        with open(os.path.join("chiprun_out", "chip_smoke.json"), "w") as f:
            json.dump(smoke.record, f, indent=1, default=str)
        smoke.close()
    smoke.say(json.dumps({k: smoke.record[k] for k in (
        "total_wall_s", "compile_s_total", "peak_bytes_in_use")}))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
