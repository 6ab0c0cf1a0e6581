"""Fused Adam + stochastic weight averaging — the OpenFold training step.

Reference: ``apex/contrib/openfold_triton/fused_adam_swa.py`` (494 LoC of
Triton): one kernel that, per parameter, (a) runs the Adam update on the
fp32 master, (b) writes the bf16 compute copy, and (c) folds the fresh
master into the SWA exponential average — three parameter banks touched
in one pass, with three selectable Adam math modes (Apex / ApexW /
PyTorch; they differ in where weight decay and bias correction land).

TPU-native: the same three-bank update as one jitted pytree transform —
XLA fuses the chain exactly like the Triton kernel fuses it (the package
name drops the ``_triton`` suffix: no Triton on TPU). SWA math
(``_swa_math``): ``swa = param`` on the first averaged step, else
``swa += (1 - decay) * (param - swa)``.
"""
from __future__ import annotations

import enum
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

Pytree = Any


class AdamMathType(enum.Enum):
    ApexAdam = 0
    ApexAdamW = 1
    PyTorchAdam = 2


class FusedAdamSWAState(NamedTuple):
    step: jax.Array  # i32
    n_averaged: jax.Array  # i32
    exp_avg: Pytree  # fp32 moments
    exp_avg_sq: Pytree


class FusedAdamSWA:
    """Functional spelling of the reference optimizer: ``step`` takes and
    returns the three parameter banks (fp32 masters, bf16 compute copies,
    SWA averages) explicitly. ``swa_decay_rate`` is the EMA decay; the
    first step copies (reference ``_swa_math``)."""

    def __init__(self, swa_decay_rate: float, lr: float = 1e-3,
                 bias_correction: bool = True,
                 betas: Tuple[float, float] = (0.9, 0.999), eps: float = 1e-8,
                 adam_math_mode: AdamMathType = AdamMathType.PyTorchAdam,
                 weight_decay: float = 0.0):
        if not isinstance(adam_math_mode, AdamMathType):
            raise ValueError(f"Unknown Adam math mode {adam_math_mode}")
        self.swa_decay_rate = swa_decay_rate
        self.lr = lr
        self.bias_correction = bias_correction
        self.betas = betas
        self.eps = eps
        self.adam_math_mode = adam_math_mode
        self.weight_decay = weight_decay

    def init(self, params: Pytree) -> FusedAdamSWAState:
        zeros = lambda: jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return FusedAdamSWAState(
            step=jnp.int32(0), n_averaged=jnp.int32(0),
            exp_avg=zeros(), exp_avg_sq=zeros(),
        )

    @jax.named_scope("apex_tpu.optimizer_step")
    def step(self, grads: Pytree, state: FusedAdamSWAState, params: Pytree,
             compute_params: Pytree, swa_params: Pytree, lr=None):
        """One fused Adam+SWA step. ``params`` fp32 masters; grads may be
        the compute dtype (cast up, reference kernel loads as fp32).
        Returns ``(params, compute_params, swa_params, state)``."""
        lr = jnp.asarray(self.lr if lr is None else lr, jnp.float32)
        b1, b2 = self.betas
        t = state.step + 1
        tf = t.astype(jnp.float32)
        if self.bias_correction:
            c1 = 1.0 - b1 ** tf
            c2 = 1.0 - b2 ** tf
        else:
            c1 = jnp.float32(1.0)
            c2 = jnp.float32(1.0)
        wd = self.weight_decay
        mode = self.adam_math_mode
        decay = self.swa_decay_rate
        first = state.n_averaged == 0

        def leaf(p, g, m, v, cp, sp):
            p32 = p.astype(jnp.float32)
            g32 = g.astype(jnp.float32)
            if mode in (AdamMathType.ApexAdam, AdamMathType.PyTorchAdam):
                g32 = g32 + wd * p32
            m = b1 * m + (1.0 - b1) * g32
            v = b2 * v + (1.0 - b2) * g32 * g32
            if mode == AdamMathType.PyTorchAdam:
                denom = jnp.sqrt(v) / jnp.sqrt(c2) + self.eps
                new_p = p32 - (lr / c1) * (m / denom)
            else:
                update = (m / c1) / (jnp.sqrt(v / c2) + self.eps)
                if mode == AdamMathType.ApexAdamW:
                    update = update + wd * p32
                new_p = p32 - lr * update
            new_sp = jnp.where(
                first, new_p,
                sp.astype(jnp.float32)
                + (1.0 - decay) * (new_p - sp.astype(jnp.float32)))
            return (new_p.astype(p.dtype), m, v, new_p.astype(cp.dtype),
                    new_sp.astype(sp.dtype))

        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state.exp_avg)
        flat_v = treedef.flatten_up_to(state.exp_avg_sq)
        flat_cp = treedef.flatten_up_to(compute_params)
        flat_sp = treedef.flatten_up_to(swa_params)
        outs = [leaf(*args) for args in
                zip(flat_p, flat_g, flat_m, flat_v, flat_cp, flat_sp)]
        unflat = lambda i: jax.tree_util.tree_unflatten(
            treedef, [o[i] for o in outs])
        new_state = FusedAdamSWAState(
            step=t, n_averaged=state.n_averaged + 1,
            exp_avg=unflat(1), exp_avg_sq=unflat(2),
        )
        return unflat(0), unflat(3), unflat(4), new_state
