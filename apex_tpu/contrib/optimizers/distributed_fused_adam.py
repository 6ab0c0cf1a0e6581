"""DistributedFusedAdam — ZeRO-2 Adam over a mesh axis.

Reference: ``apex/contrib/optimizers/distributed_fused_adam.py:273-3598`` —
the largest single component in apex.contrib. Its moving parts and their
TPU-native spellings:

==========================================================  ==================
reference mechanism                                          here
==========================================================  ==================
params flattened into fixed-size buckets (``:273-283``)      one padded flat
                                                             fp32 buffer
                                                             (``ShardedLayout``)
bucketed ``reduce_scatter_tensor`` grad sync overlapped      ``lax.psum_scatter``
with backward via hooks (``:875-924, :1920``)                (XLA overlaps)
optional all-reduce over the redundant group (``:1920``)     ``lax.psum`` over
                                                             ``redundant_axis``
shard-local multi-tensor Adam kernel (``:2580``)             shard-local fused
                                                             update (XLA-fused;
                                                             the chunked Pallas
                                                             kernel of the
                                                             single-device
                                                             ``packed=True``
                                                             path is the
                                                             planned upgrade —
                                                             see ``_sharded``
                                                             module docstring)
param ``all_gather`` overlapped with next forward            ``lax.all_gather``
(``:926-960``)                                               (XLA overlaps)
grad-norm / clip / unscale integration (``:2289-2426``)      ``max_grad_norm``
                                                             + ``grad_scale``/
                                                             ``found_inf``
v1 (gather-on-root) / v2 (per-rank shard) checkpoints        ``state_dict``
(``:2956-3555``)                                             v1/v2 formats
==========================================================  ==================

Usage — ``step`` must run inside ``shard_map`` binding ``distributed_axis``;
state is carried as global ``(padded,)`` buffers sharded with
``opt.state_specs()``::

    opt = DistributedFusedAdam(lr=1e-3, distributed_size=8)
    state = opt.init(params)                      # global, outside shard_map
    @jax.jit
    def train_step(params, state, batch):
        def shard_fn(params, state, batch):
            grads = jax.grad(loss)(params, batch)   # per-device local grads
            return opt.step(grads, state, params)
        return shard_map(shard_fn, mesh=mesh,
                         in_specs=(P(), opt.state_specs(), P("data", ...)),
                         out_specs=(P(), opt.state_specs()))(params, state, batch)

Per-device optimizer-state memory is ``padded / distributed_size`` elements
per buffer — the ZeRO-2 1/dp sharding, visible in the NamedSharding of the
returned state.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...optimizers._common import resolve_scale, skip_on_overflow
from ._sharded import Pytree, ShardedLayout


class DistributedFusedAdamState(NamedTuple):
    step: jax.Array  # i32 scalar, replicated
    exp_avg: jax.Array  # (padded,) sharded over distributed_axis
    exp_avg_sq: jax.Array  # (padded,) sharded
    param_shard: Optional[jax.Array]  # (padded,) fp32 masters when store_params


class DistributedFusedAdam:
    """ZeRO-2 Adam/AdamW (see module docstring for the reference map).

    Args mirror ``distributed_fused_adam.py:292-376``. Mechanics the XLA
    compiler owns are accepted and ignored (documented): ``overlap_grad_sync``
    / ``overlap_param_sync`` (latency-hiding scheduler), ``bucket_cap_mb`` /
    ``pipeline_size`` (collective combiner), ``contiguous_*_buffer`` (XLA
    buffer placement), ``nccl_ub`` (no NCCL).

    ``distributed_size`` replaces ``distributed_process_group``: the size of
    the mesh axis the state is sharded over (needed statically for shapes).
    ``redundant_axis`` replaces ``redundant_process_group`` — a mesh axis the
    reduced gradients are additionally psum-averaged over (state is
    replicated, not sharded, along it).
    """

    def __init__(
        self,
        lr: float = 1e-3,
        bias_correction: bool = True,
        betas: Tuple[float, float] = (0.9, 0.999),
        eps: float = 1e-8,
        adam_w_mode: bool = True,
        weight_decay: float = 0.0,
        amsgrad: bool = False,
        *,
        distributed_size: int,
        distributed_axis: str = "data",
        redundant_axis: Optional[str] = None,
        dtype=jnp.float32,
        grad_sync_dtype=None,
        param_sync_dtype=None,
        average_grad_sync: bool = True,
        overlap_grad_sync: bool = True,
        overlap_param_sync: bool = False,
        bucket_cap_mb: float = 100.0,
        pipeline_size: int = 2,
        contiguous_param_buffer: bool = False,
        contiguous_grad_buffer: bool = False,
        store_params: bool = True,
        store_param_remainders: bool = False,
        max_grad_norm: float = 0.0,
        capturable: bool = True,
    ):
        if amsgrad:
            raise RuntimeError("DistributedFusedAdam does not support AMSGrad.")
        if store_param_remainders:
            raise NotImplementedError(
                "store_param_remainders is a CUDA bit-packing trick; on TPU "
                "store_params=True already holds exact fp32 masters."
            )
        del overlap_grad_sync, overlap_param_sync, bucket_cap_mb, pipeline_size
        del contiguous_param_buffer, contiguous_grad_buffer, capturable
        self.lr = lr
        self.bias_correction = bias_correction
        self.betas = betas
        self.eps = eps
        self.adam_w_mode = adam_w_mode
        self.weight_decay = weight_decay
        self.distributed_size = distributed_size
        self.distributed_axis = distributed_axis
        self.redundant_axis = redundant_axis
        self.dtype = jnp.dtype(dtype)
        self.grad_sync_dtype = jnp.dtype(grad_sync_dtype or dtype)
        self.param_sync_dtype = jnp.dtype(param_sync_dtype or dtype)
        self.average_grad_sync = average_grad_sync
        self.store_params = store_params
        self.max_grad_norm = max_grad_norm
        self._layout: Optional[ShardedLayout] = None

    # -- layout ------------------------------------------------------------
    def layout_for(self, params: Pytree) -> ShardedLayout:
        if self._layout is None:
            self._layout = ShardedLayout(params, self.distributed_size)
        return self._layout

    def init(self, params: Pytree) -> DistributedFusedAdamState:
        """Global init (outside shard_map): (padded,) buffers to be sharded
        by ``state_specs()``. Mirrors the lazy state init at first step
        (reference ``:2427``)."""
        layout = self.layout_for(params)
        return DistributedFusedAdamState(
            step=jnp.int32(0),
            exp_avg=layout.zeros(jnp.float32),
            exp_avg_sq=layout.zeros(jnp.float32),
            param_shard=layout.flatten(params, jnp.float32)
            if self.store_params
            else None,
        )

    def state_specs(self) -> DistributedFusedAdamState:
        """PartitionSpecs for carrying the state through shard_map."""
        ax = self.distributed_axis
        return DistributedFusedAdamState(
            step=P(),
            exp_avg=P(ax),
            exp_avg_sq=P(ax),
            param_shard=P(ax) if self.store_params else None,
        )

    # -- grad sync ---------------------------------------------------------
    def _reduce_grads(self, grads: Pytree, layout: ShardedLayout, inv_scale):
        """flatten -> psum_scatter over the distributed axis (-> psum over the
        redundant axis) -> fp32 unscaled local shard.

        The reference's ``_start_bucket_grad_sync`` (``:1920``): one
        ``reduce_scatter_tensor`` per bucket plus an all-reduce over the
        redundant group, average semantics by pre-division.
        """
        flat = layout.flatten(grads, self.grad_sync_dtype)
        denom = 1.0
        if self.average_grad_sync:
            denom *= self.distributed_size
        shard = jax.lax.psum_scatter(
            flat, self.distributed_axis, scatter_dimension=0, tiled=True
        )
        if self.redundant_axis is not None:
            if self.average_grad_sync:
                denom *= jax.lax.psum(1, self.redundant_axis)
            shard = jax.lax.psum(shard, self.redundant_axis)
        shard = shard.astype(jnp.float32) * inv_scale
        if denom != 1.0:
            shard = shard / denom
        return shard

    def _clip_coef(self, grad_shard):
        """Global grad-norm clip factor from the *sharded* grads — exact, and
        1/dp the flops of a full-grad norm (reference clip integration
        ``:2289-2426``)."""
        if self.max_grad_norm <= 0:
            return jnp.float32(1.0)
        sq = jax.lax.psum(
            jnp.sum(grad_shard.astype(jnp.float32) ** 2), self.distributed_axis
        )
        norm = jnp.sqrt(sq)
        return jnp.minimum(1.0, self.max_grad_norm / jnp.maximum(norm, 1e-12))

    # -- shared shard plumbing (used by DistributedFusedLAMB too) ----------
    def _param_shard_f32(self, state, params, layout: ShardedLayout):
        """The fp32 master shard: stored state, or sliced out of the
        replicated params when ``store_params=False``."""
        if self.store_params:
            return state.param_shard
        flat = layout.flatten(params, jnp.float32)
        idx = jax.lax.axis_index(self.distributed_axis)
        return jax.lax.dynamic_slice(
            flat, (idx * layout.shard_size,), (layout.shard_size,)
        )

    def _gather_params(self, new_p32, params, layout: ShardedLayout):
        """all_gather the updated shard and rebuild the param pytree
        (the reference's overlapped param sync, ``:926-960``)."""
        gathered = jax.lax.all_gather(
            new_p32.astype(self.param_sync_dtype),
            self.distributed_axis,
            axis=0,
            tiled=True,
        )
        return layout.unflatten(gathered)

    # -- step --------------------------------------------------------------
    def _stepped(self, grads, state, params, lr, wd, inv_scale):
        layout = self.layout_for(params)
        g = self._reduce_grads(grads, layout, inv_scale)
        g = g * self._clip_coef(g)
        p32 = self._param_shard_f32(state, params, layout)

        beta1, beta2 = self.betas
        new_step = state.step + 1
        lr = jnp.asarray(lr, jnp.float32)
        if self.bias_correction:
            t = new_step.astype(jnp.float32)
            bc1 = 1.0 - beta1 ** t
            bc2 = 1.0 - beta2 ** t
        else:
            bc1 = bc2 = jnp.float32(1.0)
        if not self.adam_w_mode and wd != 0.0:
            g = g + wd * p32
        m = beta1 * state.exp_avg + (1.0 - beta1) * g
        v = beta2 * state.exp_avg_sq + (1.0 - beta2) * g * g
        update = (m / bc1) / (jnp.sqrt(v / bc2) + self.eps)
        if self.adam_w_mode and wd != 0.0:
            update = update + wd * p32
        new_p32 = p32 - lr * update
        new_params = self._gather_params(new_p32, params, layout)
        new_state = DistributedFusedAdamState(
            step=new_step,
            exp_avg=m,
            exp_avg_sq=v,
            param_shard=new_p32 if self.store_params else None,
        )
        return new_params, new_state

    @jax.named_scope("apex_tpu.optimizer_step")
    def step(
        self,
        grads: Pytree,
        state: DistributedFusedAdamState,
        params: Pytree,
        lr: Optional[jax.Array] = None,
        weight_decay: Optional[float] = None,
        found_inf: Optional[jax.Array] = None,
        grad_scale=None,
    ) -> Tuple[Pytree, DistributedFusedAdamState]:
        """One ZeRO-2 step. Must run inside shard_map binding
        ``distributed_axis`` (and ``redundant_axis`` if configured)."""
        lr = self.lr if lr is None else lr
        wd = self.weight_decay if weight_decay is None else weight_decay
        inv_scale = resolve_scale(grad_scale)
        return skip_on_overflow(
            found_inf,
            lambda: self._stepped(grads, state, params, lr, wd, inv_scale),
            (params, state),
        )

    # -- checkpointing -----------------------------------------------------
    # Reference formats (":2956-3555"): v1 gathers every shard onto the root
    # rank into a dense state_dict; v2 saves each rank's shard. Under SPMD the
    # state is already one global (padded,) array whose shards live on the
    # devices, so both formats are host-side reshapes of the same thing.

    def state_dict(self, state: DistributedFusedAdamState, format: str = "v2"):
        """Host-side checkpoint dict. ``v2``: per-shard ``(n_shards,
        shard_size)`` arrays (the reference's per-rank shard format); ``v1``:
        dense ``(padded,)`` arrays (gather-on-root format)."""
        layout = self._layout
        if layout is None:
            raise RuntimeError("state_dict before init/step: layout unknown")
        if format not in ("v1", "v2"):
            raise ValueError(f"unknown checkpoint format {format!r} (want 'v1'/'v2')")

        def pack(buf):
            a = np.asarray(buf)
            return a.reshape(layout.n_shards, layout.shard_size) if format == "v2" else a

        out = {
            "format": format,
            "step": int(np.asarray(state.step)),
            "exp_avg": pack(state.exp_avg),
            "exp_avg_sq": pack(state.exp_avg_sq),
        }
        if self.store_params:
            out["param_shard"] = pack(state.param_shard)
        return out

    def load_state_dict(self, sd) -> DistributedFusedAdamState:
        """Rebuild state from either checkpoint format (round-trip of
        ``state_dict``)."""
        def unpack(a):
            return jnp.asarray(np.asarray(a).reshape(-1), jnp.float32)

        if self.store_params and "param_shard" not in sd:
            raise ValueError(
                "checkpoint has no param_shard but store_params=True — it was "
                "written by an optimizer configured with store_params=False"
            )
        return DistributedFusedAdamState(
            step=jnp.int32(sd["step"]),
            exp_avg=unpack(sd["exp_avg"]),
            exp_avg_sq=unpack(sd["exp_avg_sq"]),
            param_shard=unpack(sd["param_shard"]) if self.store_params else None,
        )
