"""fp8 GPT training — the e4m3/e5m2 delayed-scaling recipe end-to-end.

The reference exposes fp8's communicator half (the amax-reduction group,
``apex/transformer/parallel_state.py:280-292``); the GEMMs live in
TransformerEngine. Here both halves are in-tree: this example trains a
small GPT with every projection GEMM on
``apex_tpu.fused_dense.fp8_fused_dense_qgrad`` (e4m3 forward, e5m2
gradients, delayed scaling), the per-layer states threaded through the
layer scan and the gradient amaxes recovered from the carrier
cotangents — the full TE-style loop in ~40 lines of user code.

    python fp8_training.py                 # on the TPU chip
    python fp8_training.py --cpu 1         # CI smoke on the CPU backend

On chips without a native fp8 MXU (v5e) the quantized GEMMs upcast and
run at ~0.9x bf16 — the recipe's value there is the format/state
plumbing; fp8-capable chips inherit the speedup unchanged.
"""
from __future__ import annotations

import argparse
import functools


def parse():
    p = argparse.ArgumentParser(description="fp8 GPT training example")
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--vocab", type=int, default=2048)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--cpu", type=int, default=0, metavar="N",
                   help="force a CPU backend with N virtual devices")
    return p.parse_args()


def main():
    args = parse()
    if args.cpu:
        import os

        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.cpu}"
        )
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax
    import jax.numpy as jnp

    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer.testing import (
        GPTConfig,
        gpt_loss,
        init_gpt_fp8_carriers,
        init_gpt_fp8_states,
        init_gpt_params,
        record_gpt_grad_amaxes,
    )

    cfg = GPTConfig(
        num_layers=args.layers, hidden_size=args.hidden,
        num_attention_heads=args.heads, vocab_size=args.vocab,
        max_position_embeddings=args.seq, hidden_dropout=0.0,
        attention_dropout=0.0, compute_dtype=jnp.bfloat16, fp8=True,
    )
    params = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16),
        init_gpt_params(cfg, jax.random.PRNGKey(0)),
    )
    opt = FusedAdam(lr=args.lr, master_weights=True)
    opt_state = opt.init(params)
    fp8_states = init_gpt_fp8_states(cfg)

    data_key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(
        data_key, (args.batch, args.seq), 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)

    # donate params + optimizer state (masters/moments updated in place);
    # the fp8 state tree stays undonated — donating its small nested
    # buffers trips a TPU backend INVALID_ARGUMENT (seen on the v5e), and
    # at KB size copying it is free
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, fp8_states):
        carriers = init_gpt_fp8_carriers(cfg)

        def loss_fn(p, c):
            return gpt_loss(cfg, p, tokens, labels,
                            fp8_states=fp8_states, fp8_carriers=c)

        (loss, new_states), (grads, amaxes) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(params, carriers)
        new_states = record_gpt_grad_amaxes(cfg, new_states, amaxes)
        params, opt_state = opt.step(grads, opt_state, params)
        return params, opt_state, new_states, loss

    for step in range(args.steps):
        params, opt_state, fp8_states, loss = train_step(
            params, opt_state, fp8_states)
        if step % 5 == 0 or step == args.steps - 1:
            s = fp8_states["qkv"]
            print(
                f"step {step:3d}  loss {float(loss):.4f}  "
                f"x_scale {float(s.x.scale[0]):.3g}  "
                f"g_scale {float(s.g.scale[0]):.3g}",
                flush=True,
            )
    print(f"final loss: {float(loss):.4f}")
    return float(loss)


if __name__ == "__main__":
    main()
