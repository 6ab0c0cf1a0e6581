"""Training cells: the program's own entry points, built the way
``chip_smoke.py`` builds them, with the batch as an argument of the step.

A recipe returns a :class:`TrainProgram`: ONE compiled step with its state.
Set-up drives it through its first steps from the seed, reads what the
correctness check needs from its state, and hands the same object to the
measured window.
"""
from __future__ import annotations

import collections
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops, reference, traffic, weights


def gpt_config(config: Dict[str, Any], **kw):
    """The program's ``GPTConfig`` for a configuration file, bf16 compute,
    no dropout; ``kw`` are the recipe's own switches."""
    from apex_tpu.transformer.testing import GPTConfig

    d = weights.model_dims(config)
    return GPTConfig(
        num_layers=d["layers"], num_attention_heads=d["heads"],
        hidden_size=d["hidden"], ffn_hidden_size=d["ffn"],
        vocab_size=d["vocab"], max_position_embeddings=d["positions"],
        layernorm_epsilon=weights.layer_norm_eps(config), hidden_dropout=0.0, attention_dropout=0.0,
        compute_dtype=jnp.bfloat16, **kw)


class TrainProgram:
    """One compiled step and the state it carries.

    The norms it reads from its state are the benchmark's own measurement
    (``reference.tensor_norms``, by published tensor), not the program's.

    ``step_fn(*state, tokens, labels) -> (*state, loss)`` is jitted here,
    state donated. ``moments``/``masters`` pick, from the state, the first
    moment and the float32 parameters as parameter-shaped trees."""

    def __init__(self, step_fn: Callable, state: tuple, *,
                 moments: Callable, masters: Callable, opt_step: Callable,
                 init0: Callable, heads: int, batch_sharding=None):
        self.state = tuple(state)
        self._n = len(self.state)
        self.rejit(step_fn)
        self._opt_step = opt_step
        self._batch_sharding = batch_sharding
        self._read_grad1 = jax.jit(
            lambda st: reference.tensor_norms(moments(st), heads))
        self._read_change = jax.jit(
            lambda st, key: reference.tensor_diff_norms(
                masters(st), init0(key), heads))
        self.compiled = None
        self.memory: Dict[str, int] = {}

    def rejit(self, step_fn: Callable) -> None:
        """(Re)build the jitted step; the tests plant faults through it."""
        self.step_fn = step_fn
        self._jit = jax.jit(step_fn, donate_argnums=tuple(range(self._n)))

    def put(self, tokens: np.ndarray, labels: np.ndarray):
        if self._batch_sharding is not None:
            return (jax.device_put(tokens, self._batch_sharding),
                    jax.device_put(labels, self._batch_sharding))
        return jnp.asarray(tokens), jnp.asarray(labels)

    def compile(self, tokens, labels) -> None:
        self.compiled = self._jit.lower(
            *self.state, tokens, labels).compile()
        ma = self.compiled.memory_analysis()
        if ma is not None:
            self.memory = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes)}

    def run(self, tokens, labels):
        """One step through the compiled program; returns the loss (on
        the device)."""
        out = self.compiled(*self.state, tokens, labels)
        self.state, loss = tuple(out[:self._n]), out[self._n]
        return loss

    def grad1_norms(self) -> Dict[str, float]:
        return reference.by_tensor(self._read_grad1(self.state))

    def change_norms(self, key) -> Dict[str, float]:
        return reference.by_tensor(self._read_change(self.state, key))

    def optimizer_steps(self) -> int:
        return int(self._opt_step(self.state))


# ---------------------------------------------------------------------------
# recipes: each is chip_smoke.py's, with tokens and labels as arguments
# ---------------------------------------------------------------------------
def amp_o2_fused_adam(config, mix, seed, devices, interpret) -> TrainProgram:
    """amp O2 -> ``scaled_value_and_grad`` -> ``FusedAdam(packed=True)
    .step(found_inf=)`` -> ``update_scale`` (``chip_smoke.train_program``).
    O2 keeps bf16 model weights and float32 masters made from them, so
    the weights are made in bf16 and the reference starts from the same
    values in float32."""
    from apex_tpu import amp
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer.testing import gpt_loss

    cfg = gpt_config(
        config, recompute_granularity="selective_elementwise",
        layer_unroll=-1, fused_block=True, fused_block_interpret=interpret,
        use_flash_attention=True if interpret else None)
    hyper = config["train"]["optimizer"]
    params = weights.init_params(config, seed, jnp.bfloat16)
    opt = FusedAdam(lr=hyper["lr"], betas=(hyper["b1"], hyper["b2"]),
                    eps=hyper["eps"], packed=True, packed_interpret=interpret)
    params, opt, amp_state = amp.initialize(params, opt, opt_level="O2")
    scaler = amp_state.scaler(0)
    grad_fn = amp.scaled_value_and_grad(
        lambda p, tokens, labels: gpt_loss(cfg, p, tokens, labels), scaler)

    def train_step(params, opt_state, sstate, tokens, labels):
        loss, grads, sstate = grad_fn(sstate, params, tokens, labels)
        params, opt_state = opt.step(grads, opt_state, params,
                                     found_inf=sstate.found_inf)
        return params, opt_state, scaler.update_scale(sstate), loss

    opt_state = jax.jit(opt.init)(params)
    spec = opt_state.spec
    return TrainProgram(
        train_step, (params, opt_state, amp_state.scaler_state(0)),
        moments=lambda st: spec.unpack(st[1].exp_avg, cast=False),
        masters=lambda st: spec.unpack(st[1].master_params, cast=False),
        opt_step=lambda st: st[1].step,
        init0=lambda key: weights.init_from_key(config, key, jnp.bfloat16),
        heads=weights.model_dims(config)["heads"])


def fused_lamb(config, mix, seed, devices, interpret) -> TrainProgram:
    """BERT pretraining step with ``FusedLAMB`` as ``bench.bench_bert_lamb``
    builds it: float32 parameters, bf16 compute, selective recompute, the
    optimizer's default (per-tensor, not packed) path. The packed path read
    34.3k tokens/s against this one's rate on the chip (PERF.md): its
    pack/unpack copies cost more than its sweeps save."""
    from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss
    from apex_tpu.optimizers import FusedLAMB
    from apex_tpu.transformer.testing.standalone_transformer_lm import (
        bert_forward,
    )

    cfg = gpt_config(config, recompute_granularity="selective",
                      layer_unroll=-1,
                      use_flash_attention=True if interpret else None)
    hyper = config["train"]["optimizer"]
    params = weights.init_params(config, seed, jnp.float32)
    opt = FusedLAMB(lr=hyper["lr"], betas=(hyper["b1"], hyper["b2"]),
                    eps=hyper["eps"], weight_decay=hyper["wd"],
                    max_grad_norm=hyper["max_grad_norm"])

    def loss_fn(p, tokens, labels):
        logits, _ = bert_forward(cfg, p, tokens,
                                 padding_mask=jnp.ones_like(tokens))
        losses = softmax_cross_entropy_loss(
            logits.reshape(-1, cfg.vocab_size).astype(jnp.float32),
            labels.reshape(-1), padding_idx=-1)
        return jnp.mean(losses)

    def train_step(params, opt_state, tokens, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, labels)
        params, opt_state = opt.step(grads, opt_state, params)
        return params, opt_state, loss

    return TrainProgram(
        train_step, (params, jax.jit(opt.init)(params)),
        moments=lambda st: st[1].exp_avg,
        masters=lambda st: st[0],
        opt_step=lambda st: st[1].step,
        init0=lambda key: weights.init_from_key(config, key, jnp.float32),
        heads=weights.model_dims(config)["heads"])


def ddp_fused_adam(config, mix, seed, devices, interpret) -> TrainProgram:
    """The train step data-parallel over ``devices``: ``GradBuckets`` +
    ``DistributedDataParallel.reduce_flat`` + ``found_inf_flat`` + one
    ``step_flat`` sweep (``chip_smoke.dp_train_program``). The float32
    master buffer is the parameter store."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from apex_tpu.amp import LossScaler
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import DistributedDataParallel, GradBuckets
    from apex_tpu.transformer.testing import gpt_loss

    n_dev = len(devices)
    cfg = gpt_config(
        config, recompute_granularity="selective_elementwise",
        layer_unroll=-1, fused_block=True, fused_block_interpret=interpret,
        use_flash_attention=True if interpret else None)
    hyper = config["train"]["optimizer"]
    mesh = Mesh(np.asarray(devices), ("data",))
    rep = NamedSharding(mesh, P())
    params = weights.init_params(config, seed, jnp.bfloat16, sharding=rep)
    buckets = GradBuckets(params, bucket_cap_mb=BUCKET_CAP_MB)
    opt = FusedAdam(lr=hyper["lr"], betas=(hyper["b1"], hyper["b2"]),
                    eps=hyper["eps"], master_weights=True, packed=True,
                    packed_interpret=interpret, packed_spec=buckets.spec)
    opt_state = jax.jit(opt.init, out_shardings=rep)(params)
    del params
    ddp = DistributedDataParallel(axis_name="data", gradient_average=False,
                                  bucket_cap_mb=BUCKET_CAP_MB)
    scaler = LossScaler(loss_scale="dynamic")
    sstate = jax.device_put(scaler.init_state(), rep)

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

    step = jax.shard_map(shard_step, mesh=mesh,
                         in_specs=(P(), P(), P("data"), P("data")),
                         out_specs=(P(), P(), P()), check_vma=False)
    spec = buckets.spec
    return TrainProgram(
        step, (opt_state, sstate),
        moments=lambda st: spec.unpack(st[0].exp_avg, cast=False),
        masters=lambda st: spec.unpack(st[0].master_params, cast=False),
        opt_step=lambda st: st[0].step,
        init0=lambda key: weights.init_from_key(config, key, jnp.bfloat16),
        batch_sharding=NamedSharding(mesh, P("data")),
        heads=weights.model_dims(config)["heads"])


WARM_STEPS = 3           # the first steps from the seed, which the check follows
BLOCK_ROWS = 2           # rows a chip holds at a time in the float32 reference
STEPS_IN_FLIGHT = 8      # dispatched and not yet fetched, in the window
TRACED_STEPS = 4         # loop iterations a --trace 1 run traces
BUCKET_CAP_MB = 25.0     # DistributedDataParallel's own default

RECIPES: Dict[str, Callable[..., TrainProgram]] = {
    "amp_o2_fused_adam": amp_o2_fused_adam,
    "fused_lamb": fused_lamb,
    "ddp_fused_adam": ddp_fused_adam,
}


def build_program(config, mix, seed, devices, interpret=False):
    train = config["train"]
    name = (train["data_parallel_recipe"] if len(devices) > 1
            else train["recipe"])
    return RECIPES[name](config, mix, seed, devices, interpret)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
def run(ctx, *, program_factory: Callable = build_program) -> Dict[str, Any]:
    """Set-up, window and check of one training cell. ``ctx`` is the
    harness's :class:`benchmark.run.Context`."""
    config, devices = ctx.config, ctx.devices
    mix = traffic.train_mix(ctx.mix, ctx.chips)
    dims = weights.model_dims(config)
    b, s = mix["batch"], mix["seq"]
    feed = lambda i: traffic.train_batch(
        ctx.seed, i, b, s, dims["vocab"], mix["labels"])

    program = program_factory(config, ctx.mix, ctx.seed, devices,
                              ctx.interpret)
    jax.block_until_ready(program.state)
    ctx.mark("state")
    first = program.put(*feed(0))
    program.compile(*first)
    ctx.mark("build")

    # the first steps from the seed, through the window's own call and feed
    warm = WARM_STEPS
    steps0 = program.optimizer_steps()
    losses: List[Any] = [program.run(*first)]
    grad1 = program.grad1_norms()
    for i in range(1, warm):
        losses.append(program.run(*program.put(*feed(i))))
    change = program.change_norms(weights.key_from_seed(ctx.seed))
    seen = {"losses": [float(x) for x in losses], "grad1_norms": grad1,
            "change_norms": change}
    ctx.mark("warm")

    # the window
    ctx.window_open()
    t0 = time.perf_counter()
    i, window_losses = warm, []
    in_flight: collections.deque = collections.deque()   # losses not fetched
    tracer = ctx.tracer(after_s=0.0 if ctx.seconds < 4 else 2.0,
                        length=TRACED_STEPS)
    after_trace = None      # (instant, step) at which the tracer was done
    while True:
        tracer.tick()
        if after_trace is None and tracer.state == "done":
            after_trace = (time.perf_counter(), i)
        with ctx.span("bench.feed"):
            tokens, labels = program.put(*feed(i))
        with ctx.span("bench.dispatch"):
            loss = program.run(tokens, labels)
        i += 1
        in_flight.append(loss)
        # several steps stay in flight, so that a stall of this thread
        # shorter than their sum does not idle the device (two BERT runs
        # of 19 lost 0.7 s and 1.7 s with fewer); the oldest loss is fetched
        if len(in_flight) > STEPS_IN_FLIGHT:
            with ctx.span("bench.loss_fetch"):
                window_losses.append(float(in_flight.popleft()))
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    with ctx.span("bench.loss_fetch"):
        window_losses.extend(float(x) for x in in_flight)
    t1 = time.perf_counter()
    window_s = t1 - t0
    ctx.window_close()
    tracer.close()
    n_steps = i - warm
    # a traced run's per-layer rates are taken over the part of the window
    # after the profiler stopped: starting and stopping it stalls the loop
    rate_steps, rate_s = ((n_steps, window_s) if after_trace is None
                          else (i - after_trace[1], t1 - after_trace[0]))
    opt_steps = program.optimizer_steps() - steps0
    ctx.memory_peak()
    counters = {
        "steps": rate_steps, "window_s": rate_s, "chips": ctx.chips,
        "skipped_steps": (warm + n_steps) - opt_steps,
        "hbm_program_bytes": (
            program.memory.get("argument_bytes", 0)
            + program.memory.get("temp_bytes", 0)
            + program.memory.get("output_bytes", 0)
            - program.memory.get("alias_bytes", 0)) or None,
        "train_flops_per_step": flops.train_flops_per_step(
            dims["layers"], dims["hidden"], dims["ffn"], dims["vocab"],
            b, s, causal=config["train"]["causal"]),
        "dims": dims, "batch": b, "seq": s,
        "causal": config["train"]["causal"],
    }
    if ctx.chips > 1 and ctx.trace:
        counters["psums_per_step"] = _count_psums(program, first)
    finite = bool(np.all(np.isfinite(window_losses)))

    # the program's state goes before the reference comes
    del program, first, tokens, labels, loss, in_flight
    ctx.free()
    ctx.mark("check")
    ref = reference_steps(config, ctx.seed, [feed(k) for k in range(warm)],
                          devices=devices)
    where: Dict[str, Any] = {}
    compared = compare(seen, ref, where)
    compared["window_losses_finite"] = 1.0 if finite else 0.0
    return {
        "end_to_end": {
            "train_tokens_per_s": n_steps * b * s / window_s},
        "counters": counters, "compared": compared,
        "attempted": n_steps, "failed": 0 if finite else n_steps,
        "notes": where,
    }


def _count_psums(program, batch) -> int:
    """Cross-chip sums of the timed step, from its traced jaxpr (the
    program's own static analysis; pmean counts as its psum)."""
    from apex_tpu.analysis import collective_inventory

    jaxpr = jax.make_jaxpr(program.step_fn)(*program.state, *batch)
    return sum(1 for rec in collective_inventory(jaxpr.jaxpr)
               if rec.name == "psum")


def reference_params(config, seed):
    """What the reference starts from: the benchmark's own weights for the
    seed, in float32. Where the configuration trains from bf16 weights
    (``weights_dtype``: amp O2's masters are copies of the bf16 model
    weights) it starts from those values."""
    return reference.f32(weights.init_params(
        config, seed, jnp.dtype(config["train"]["weights_dtype"])))


def reference_steps(config, seed, batches, **faults):
    dims = weights.model_dims(config)
    return reference.train_steps(
        reference_params(config, seed), batches, heads=dims["heads"],
        causal=config["train"]["causal"],
        optimizer=config["train"]["optimizer"],
        ln_eps=weights.layer_norm_eps(config), block_rows=BLOCK_ROWS, **faults)


def compare(seen: Dict[str, Any], ref: Dict[str, Any],
            where: Optional[dict] = None) -> Dict[str, float]:
    """The numbers compared: each step's loss (relative gap), and by the
    worst leaf the gap between the program's and the reference's norm of
    the first gradient and of the parameters' change, measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger. Leaves whose reference gradient is under a thousandth of the
    median leaf's are left out of the change (they move by round-off
    alone under Adam)."""
    out: Dict[str, float] = {}
    for k, (a, r) in enumerate(zip(seen["losses"], ref["losses"])):
        out[f"loss{k + 1}_rel_gap"] = abs(a - r) / abs(r)
    g_ref = ref["grad1_norms"]
    g_med = float(np.median(list(g_ref.values())))
    out["grad1_worst_leaf_gap"], worst_g = max(
        (abs(seen["grad1_norms"][k] - g) / max(g, g_med), k)
        for k, g in g_ref.items())
    c_ref = {k: v for k, v in ref["change_norms"].items()
             if g_ref[k] >= 1e-3 * g_med}
    c_med = float(np.median(list(c_ref.values())))
    out["change_worst_leaf_gap"], worst_c = max(
        (abs(seen["change_norms"][k] - c) / max(c, c_med), k)
        for k, c in c_ref.items())
    if where is not None:
        where.update(grad1_worst_leaf=worst_g, change_worst_leaf=worst_c,
                     leaves_left_out=len(g_ref) - len(c_ref))
    return out
