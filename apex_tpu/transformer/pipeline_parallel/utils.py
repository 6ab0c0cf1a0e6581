"""Pipeline-parallel utilities.

Reference: ``apex/transformer/pipeline_parallel/utils.py`` — microbatch
calculator setup (``:58``), microbatch slicing (``:122``), TP-aware param
L2 norm (``:213``), DP loss averaging (``:242``), memory reporting
(``:253``), LM mask/position helpers (``:303``).
"""
from __future__ import annotations

from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp

from ..._vma import pvary
from ...ops.multi_tensor import multi_tensor_l2norm
from .. import parallel_state
from ..microbatches import (
    ConstantNumMicroBatches,
    RampupBatchsizeNumMicroBatches,
    build_num_microbatches_calculator,
)

Pytree = Any

_GLOBAL_NUM_MICROBATCHES_CALCULATOR = None
_GLOBAL_AUTORESUME = None


def setup_microbatch_calculator(
    rank: int,
    rampup_batch_size: Optional[List[int]],
    global_batch_size: int,
    micro_batch_size: int,
    data_parallel_size: int,
) -> None:
    """Reference ``utils.py:58-75``."""
    global _GLOBAL_NUM_MICROBATCHES_CALCULATOR
    if _GLOBAL_NUM_MICROBATCHES_CALCULATOR is not None:
        raise RuntimeError("num microbatches calculator is already initialized")
    _GLOBAL_NUM_MICROBATCHES_CALCULATOR = build_num_microbatches_calculator(
        rank, rampup_batch_size, global_batch_size, micro_batch_size,
        data_parallel_size,
    )


def _reconfigure_microbatch_calculator(
    rank, rampup_batch_size, global_batch_size, micro_batch_size,
    data_parallel_size,
) -> None:
    """Reference ``utils.py:78-89`` (testing hook)."""
    global _GLOBAL_NUM_MICROBATCHES_CALCULATOR
    _GLOBAL_NUM_MICROBATCHES_CALCULATOR = build_num_microbatches_calculator(
        rank, rampup_batch_size, global_batch_size, micro_batch_size,
        data_parallel_size,
    )


def destroy_num_microbatches_calculator() -> None:
    global _GLOBAL_NUM_MICROBATCHES_CALCULATOR
    _GLOBAL_NUM_MICROBATCHES_CALCULATOR = None


def get_num_microbatches() -> int:
    return _GLOBAL_NUM_MICROBATCHES_CALCULATOR.get()


def get_current_global_batch_size() -> int:
    return _GLOBAL_NUM_MICROBATCHES_CALCULATOR.get_current_global_batch_size()


def get_micro_batch_size() -> int:
    return _GLOBAL_NUM_MICROBATCHES_CALCULATOR.micro_batch_size


def update_num_microbatches(consumed_samples, consistency_check=True) -> None:
    _GLOBAL_NUM_MICROBATCHES_CALCULATOR.update(
        consumed_samples, consistency_check
    )


def get_autoresume():
    """Reference ``utils.py:142`` — autoresume hook stub."""
    return _GLOBAL_AUTORESUME


def listify_model(model) -> List[Any]:
    """Reference ``utils.py:115``."""
    return model if isinstance(model, list) else [model]


def get_kth_microbatch(batch: Optional[Pytree], k: int) -> Pytree:
    """Slice microbatch ``k`` out of a batch whose leaves have the global
    batch on dim 0 (reference ``utils.py:122-139``)."""
    if batch is None:
        return batch
    mbs = get_micro_batch_size()
    start, end = k * mbs, (k + 1) * mbs
    return jax.tree_util.tree_map(lambda t: t[start:end], batch)


def split_into_microbatches(batch: Pytree, num_microbatches: int) -> Pytree:
    """Reshape leaves ``[gbs, ...] -> [n, gbs/n, ...]`` for the scan-based
    schedules (TPU-native companion to :func:`get_kth_microbatch`)."""
    return jax.tree_util.tree_map(
        lambda t: t.reshape((num_microbatches, -1) + t.shape[1:]), batch
    )


def calc_params_l2_norm(params: Pytree, tp_duplicate_paths=(), axis_name=None):
    """Global L2 norm of params (reference ``utils.py:213-239``).

    The reference drops TP-duplicated params on non-zero TP ranks before the
    norm; in SPMD, pass the replicated-parameter subtree separately via
    ``tp_duplicate_paths`` filtering at the call site, or call outside
    shard_map where params are global. Uses one fused reduction sweep (the
    ``multi_tensor_l2norm`` analogue).
    """
    del tp_duplicate_paths
    norm, _ = multi_tensor_l2norm(params)
    if axis_name is not None:
        norm = jnp.sqrt(jax.lax.psum(jnp.square(norm), axis_name))
    return norm


def allreduce_sequence_parallel_grads(
    grads: Pytree,
    is_sequence_parallel_param,
    axis_name: Optional[str] = None,
) -> Pytree:
    """All-reduce grads of sequence-parallel-replicated params over TP.

    Under Megatron sequence parallelism, layernorm weights are replicated
    across TP ranks while their activations are sequence-sharded, so their
    grads must be summed across the TP group — the grad-sync loop the
    reference runs over params tagged ``sequence_parallel_enabled``
    (``apex/transformer/layers/layer_norm.py:26-50`` tagging; consumed by
    Megatron-style trainers).

    ``is_sequence_parallel_param`` is a REQUIRED predicate over the
    flattened key-path string (e.g. ``lambda p: "_ln_" in p`` for the
    standalone GPT's layernorm naming, or a closure over your modules'
    ``sequence_parallel_param_names``). It is deliberately not defaulted:
    generic name matching ("weight"/"bias") would psum grads of ordinary
    dense layers and silently corrupt the step.
    """
    a = axis_name if axis_name is not None else parallel_state.TENSOR_AXIS
    flat, treedef = jax.tree_util.tree_flatten_with_path(grads)
    out = []
    for path, leaf in flat:
        pstr = jax.tree_util.keystr(path)
        if is_sequence_parallel_param(pstr):
            out.append(jax.lax.psum(leaf, a))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def average_losses_across_data_parallel_group(losses: Sequence, axis_name=None):
    """Reference ``utils.py:242-250``: mean of the concatenated losses over
    the DP axis (inside shard_map) or locally (outside)."""
    a = axis_name if axis_name is not None else parallel_state.DATA_AXIS
    averaged = jnp.stack([jnp.asarray(l) for l in losses])
    try:
        return jax.lax.pmean(averaged, a)
    except NameError:
        return averaged


def report_memory(name: str) -> str:  # pragma: no cover - device introspection
    """Reference ``utils.py:253-262``. On TPU, reads live-buffer stats from
    the backend's memory stats when available."""
    try:
        stats = jax.local_devices()[0].memory_stats()
        mega = 1024 * 1024
        string = (
            f"{name} memory (MB) | bytes_in_use: "
            f"{stats.get('bytes_in_use', 0) / mega:.1f} | peak_bytes_in_use: "
            f"{stats.get('peak_bytes_in_use', 0) / mega:.1f} | limit: "
            f"{stats.get('bytes_limit', 0) / mega:.1f}"
        )
    except Exception:
        string = f"{name} memory stats unavailable on this backend"
    print(string, flush=True)
    return string


def print_params_min_max_norm(params: Pytree, iteration: int) -> None:
    """Reference ``utils.py:265-300`` param dump."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        l32 = leaf.astype(jnp.float32)
        print(
            f"iter {iteration} param {jax.tree_util.keystr(path)} "
            f"min {float(l32.min()):.4e} max {float(l32.max()):.4e} "
            f"norm {float(jnp.linalg.norm(l32.ravel())):.4e}",
            flush=True,
        )


def get_ltor_masks_and_position_ids(
    data: jax.Array,
    eod_token: int,
    reset_position_ids: bool = False,
    reset_attention_mask: bool = False,
    eod_mask_loss: bool = False,
):
    """Left-to-right LM masks/positions (reference ``utils.py:303-357``).

    Returns ``(attention_mask [b,1,s,s] bool where True = masked out,
    loss_mask [b,s], position_ids [b,s])``. The per-document reset options
    are implemented with cumulative-EOD arithmetic instead of the
    reference's per-example Python loop (XLA-friendly, no host sync).
    """
    b, s = data.shape
    # causal base mask: True above the diagonal = masked
    causal = jnp.triu(jnp.ones((s, s), bool), k=1)

    loss_mask = jnp.ones((b, s), jnp.float32)
    if eod_mask_loss:
        loss_mask = jnp.where(data == eod_token, 0.0, loss_mask)

    position_ids = jnp.broadcast_to(jnp.arange(s), (b, s))
    is_eod = (data == eod_token)
    # document id of each token: number of EODs strictly before it
    doc_id = jnp.cumsum(is_eod, axis=1) - jnp.where(is_eod, 1, 0)

    if reset_position_ids:
        # position within document: global pos − pos of document start.
        # an EOD at p starts a new document at p+1 (the EOD itself keeps its
        # position in the preceding document, reference utils.py:342-353)
        doc_start = jnp.where(is_eod, position_ids + 1, 0)
        doc_start = jnp.pad(doc_start[:, :-1], ((0, 0), (1, 0)))
        start_of_doc = jax.lax.associative_scan(jnp.maximum, doc_start, axis=1)
        position_ids = position_ids - start_of_doc

    attention_mask = jnp.broadcast_to(causal, (b, 1, s, s))
    if reset_attention_mask:
        # tokens may not attend across document boundaries
        same_doc = doc_id[:, None, :, None] == doc_id[:, None, None, :]
        attention_mask = attention_mask | ~same_doc

    return attention_mask, loss_mask, position_ids


def vma_tracking_active(axis_name: str) -> bool:
    """True when the enclosing shard_map tracks varying-manual-axes
    (``check_vma=True``). ``axis_index`` is varying over its axis by
    construction, so an empty vma on it means tracking is off — unlike
    probing a data value, whose vma is legitimately empty when replicated."""
    probe = jax.lax.axis_index(axis_name)
    return axis_name in getattr(probe.aval, "vma", ())


def pvary_full(tree: Pytree, axis_names: Sequence[str]) -> Pytree:
    """Mark every leaf of ``tree`` as varying over all of ``axis_names``.

    The composed-mesh (TP x PP x DP) entry pattern under
    ``shard_map(check_vma=True)``. GRADIENT CONTRACT — the transpose of
    ``pvary`` is a **psum over the axes it added**, so there are two
    regimes (pinned by ``tests/test_composed_parallelism.py`` and
    ``tests/test_tied_embedding_pipeline.py``):

    - ``value_and_grad`` of a function that calls ``pvary_full`` on its
      own inputs differentiates the PRE-pvary values: grads come back
      FULLY SYNCED (replicated-axis cotangents psummed, sharded axes kept
      per-shard). Do NOT re-psum them — :func:`sync_grads_by_spec` on top
      double-counts.
    - differentiating w.r.t. ALREADY-pvary'd values (e.g. the stage
      params inside ``pipeline_forward_backward``) skips that transpose:
      grads are per-shard partials on the replicated axes and need
      :func:`sync_grads_by_spec`.

    Together these are the library spelling of the grad-sync contract the
    reference distributes across DDP hooks
    (``apex/parallel/distributed.py:323-412``) and the TP linears'
    backward all-reduces (``tensor_parallel/layers.py:279-437``).
    """
    def leaf(x):
        missing = tuple(
            a for a in axis_names if a not in getattr(x.aval, "vma", ())
        )
        return pvary(x, missing) if missing else x

    return jax.tree_util.tree_map(leaf, tree)


def sync_grads_by_spec(grads: Pytree, pspec: Pytree, axis_names: Sequence[str]) -> Pytree:
    """psum each gradient leaf over every mesh axis its parameter is NOT
    sharded on.

    ``pspec`` mirrors ``grads``' structure with a ``PartitionSpec`` per leaf
    (the parameter shardings). A parameter sharded on an axis has distinct
    per-shard gradients (no sync); a parameter replicated over an axis
    accumulated per-device partials there that must be summed — data-parallel
    sync over ``data``, replicated-weight sync over ``tensor``/``pipeline``.

    ONLY for grads that really are per-device partials: grads taken w.r.t.
    already-pvary'd values (``pipeline_forward_backward``'s stage params)
    or produced under ``check_vma=False``. Grads from ``value_and_grad``
    of a function that pvary's its own inputs are already synced by the
    pvary transpose — syncing them again double-counts (see
    :func:`pvary_full`).
    """

    def sync(g, spec):
        sharded = set()
        for part in spec:
            if part is None:
                continue
            if isinstance(part, str):
                sharded.add(part)
            else:
                sharded.update(part)
        unsynced = tuple(a for a in axis_names if a not in sharded)
        return jax.lax.psum(g, unsynced) if unsynced else g

    return jax.tree_util.tree_map(sync, grads, pspec)


def sync_embedding_grads(grads: Pytree, axis_name: Optional[str] = None) -> Pytree:
    """All-reduce tied-embedding grads over the pipeline embedding group.

    Reference: Megatron-style trainers all-reduce the word-embedding grad
    between the first and last pipeline stages, which both hold a copy of
    the tied table (the ``_EMBEDDING_GROUP`` built at
    ``apex/transformer/parallel_state.py:319-407``; the predicate surface at
    ``:466-476``). On a mesh the "group" is a masked psum over the pipeline
    axis: contributions from stages outside the embedding group (first,
    last, and the split stage for encoder-decoder models) are zeroed, then
    summed, so every stage leaves with the combined input-embedding +
    LM-head gradient. Stages outside the group receive the synced value too
    — harmless for a replicated parameter, and required in SPMD where every
    device runs the same program.

    Use when the tied table is REPLICATED over the pipeline axis AND the
    grads are per-stage partials — a manual/``check_vma=False`` flow, or a
    custom-vjp schedule that assembles stage grads itself (the reference's
    per-rank ``weight.grad`` state). Under ``check_vma=True`` autodiff of
    a function that pvary's its inputs, the pipeline sum already happened
    in the pvary transpose (see :func:`pvary_full`) — though without the
    group masking this utility adds. When the table is vocab-sharded over
    the pipeline axis instead (the memory-lean layout — see
    ``__graft_entry__``), each stage owns distinct rows and no pipeline
    sync applies at all.
    """
    return _group_masked_psum(
        grads, parallel_state.is_rank_in_embedding_group(), axis_name
    )


def sync_position_embedding_grads(
    grads: Pytree, axis_name: Optional[str] = None
) -> Pytree:
    """All-reduce position-embedding grads over the position-embedding
    group (reference ranks [0] + split stage, ``parallel_state.py:354,
    :369-375``) — the encoder-decoder analogue of
    :func:`sync_embedding_grads` for the (untied) position table."""
    return _group_masked_psum(
        grads, parallel_state.is_rank_in_position_embedding_group(), axis_name
    )


def _group_masked_psum(grads: Pytree, in_group, axis_name: Optional[str]) -> Pytree:
    """Masked all-reduce over the pipeline axis: contributions from ranks
    outside ``in_group`` are zeroed, then summed (the mesh spelling of a
    reference sub-group all-reduce)."""
    a = axis_name if axis_name is not None else parallel_state.PIPELINE_AXIS

    def sync(g):
        masked = jnp.where(in_group, g, jnp.zeros_like(g))
        return jax.lax.psum(masked, a)

    return jax.tree_util.tree_map(sync, grads)


def mask_to_axis_root(value: jax.Array, axis_names) -> jax.Array:
    """Zero ``value`` on every rank except index 0 of each axis in
    ``axis_names``.

    Companion to :func:`pvary_full`/:func:`sync_grads_by_spec`: a loss that
    is replicated in VALUE but varying in TYPE over an axis (e.g. after an
    ``all_gather`` of TP outputs) would seed one cotangent per replica,
    scaling every gradient by the axis size. Mask the loss with this
    before differentiating, then undo the mask on the *value* with
    ``jax.lax.psum(loss, axis)``. A loss that is replicated-TYPED (built
    through ``psum``/``pmean``, like the vocab-parallel CE) seeds exactly
    once by the vma rules and needs no mask — masking + psum-undo is then
    a harmless identity. (The pipeline schedules already apply the same
    masking over the pipeline axis — non-last stages contribute zero.)
    """
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    keep = jnp.bool_(True)
    for a in axis_names:
        keep = keep & (jax.lax.axis_index(a) == 0)
    return jnp.where(keep, value, jnp.zeros_like(value))
