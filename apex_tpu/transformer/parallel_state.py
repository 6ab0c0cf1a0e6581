"""Model-parallel state: the TP × PP × DP mesh registry.

Reference: ``apex/transformer/parallel_state.py`` — a registry of
``torch.distributed`` process groups for tensor/pipeline/data parallelism
plus embedding groups, virtual-pipeline rank state, and a pipeline split
rank, built rank-by-rank with NCCL/UCC communicators
(``initialize_model_parallel`` ``parallel_state.py:155-419``).

TPU-native design: there are no process groups to build. One
``jax.sharding.Mesh`` with named axes ``(pipeline, data, tensor)`` *is* the
entire group structure — a "group" is a mesh axis, a "rank" is
``jax.lax.axis_index(axis)`` inside the SPMD program, and communicator setup
(IB/socket selection, UCC backends, NCCL options — reference ``:83-153``)
collapses into XLA's ICI/DCN routing. The axis order puts ``tensor``
innermost so TP collectives ride the fastest ICI links, mirroring the
reference's layout where TP ranks are adjacent GPUs (``:186-200``).

The module keeps the reference's full getter/setter API. Rank getters are
dual-mode:

- inside ``shard_map``/``pjit`` where the axis is bound, they return the
  traced ``axis_index`` — use this in layer code;
- outside a traced context they raise unless the mesh is trivial along that
  axis, because a single SPMD controller has no "current rank".

Virtual-pipeline (interleaved schedule) rank and the pipeline split rank are
host-side Python state exactly as in the reference (``:245-258``).
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

# Canonical axis names.
PIPELINE_AXIS = "pipeline"
DATA_AXIS = "data"
TENSOR_AXIS = "tensor"

# Module-level state (the reference's module globals, ``parallel_state.py:33-80``).
_MESH: Optional[Mesh] = None
_TENSOR_MODEL_PARALLEL_WORLD_SIZE: Optional[int] = None
_PIPELINE_MODEL_PARALLEL_WORLD_SIZE: Optional[int] = None
_DATA_PARALLEL_WORLD_SIZE: Optional[int] = None
_VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK: Optional[int] = None
_VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE: Optional[int] = None
_PIPELINE_MODEL_PARALLEL_SPLIT_RANK: Optional[int] = None
_USE_FP8: bool = False


def initialize_model_parallel(
    tensor_model_parallel_size_: int = 1,
    pipeline_model_parallel_size_: int = 1,
    virtual_pipeline_model_parallel_size_: Optional[int] = None,
    pipeline_model_parallel_split_rank_: Optional[int] = None,
    use_fp8_: bool = False,
    *,
    devices: Optional[Sequence] = None,
    default_backend: Optional[str] = None,
    p2p_backend: Optional[str] = None,
) -> Mesh:
    """Build the (pipeline, data, tensor) device mesh.

    Mirrors ``apex/transformer/parallel_state.py:155-419``. ``devices``
    defaults to ``jax.devices()``; data-parallel size is inferred as
    ``len(devices) / (tp * pp)``. ``default_backend``/``p2p_backend``
    (NCCL/UCC selection, reference ``:163-211``) have no TPU meaning and are
    accepted and ignored — ICI/DCN routing is XLA's.

    Returns the mesh; it is also installed as module state for the getters
    and usable as ``with parallel_state.get_mesh(): ...``.
    """
    global _MESH, _TENSOR_MODEL_PARALLEL_WORLD_SIZE
    global _PIPELINE_MODEL_PARALLEL_WORLD_SIZE, _DATA_PARALLEL_WORLD_SIZE
    global _VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK
    global _VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE
    global _PIPELINE_MODEL_PARALLEL_SPLIT_RANK
    global _USE_FP8
    del default_backend, p2p_backend
    _USE_FP8 = bool(use_fp8_)

    devs = list(devices) if devices is not None else jax.devices()
    world = len(devs)
    tp, pp = int(tensor_model_parallel_size_), int(pipeline_model_parallel_size_)
    if world % (tp * pp) != 0:
        raise RuntimeError(
            f"world size ({world}) is not divisible by tp ({tp}) x pp ({pp})"
        )
    dp = world // (tp * pp)

    if virtual_pipeline_model_parallel_size_ is not None:
        # reference parallel_state.py:245-249 requires pp > 2 for the
        # interleaved schedule (2-stage interleaving is numerically suspect)
        if pp <= 2:
            raise RuntimeError(
                "pipeline-model-parallel size should be greater than 2 with "
                "interleaved schedule"
            )
        _VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK = 0
        _VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE = (
            virtual_pipeline_model_parallel_size_
        )
    else:
        _VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK = None
        _VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE = None
    _PIPELINE_MODEL_PARALLEL_SPLIT_RANK = pipeline_model_parallel_split_rank_

    # Mesh layout (pp, dp, tp): tp contiguous/innermost — same device
    # adjacency as the reference's group layout doc (parallel_state.py:186-200).
    mesh_devices = np.array(devs).reshape(pp, dp, tp)
    _MESH = Mesh(mesh_devices, (PIPELINE_AXIS, DATA_AXIS, TENSOR_AXIS))
    _TENSOR_MODEL_PARALLEL_WORLD_SIZE = tp
    _PIPELINE_MODEL_PARALLEL_WORLD_SIZE = pp
    _DATA_PARALLEL_WORLD_SIZE = dp
    return _MESH


def model_parallel_is_initialized() -> bool:
    """Reference ``parallel_state.py:429``."""
    return _MESH is not None


def get_mesh() -> Mesh:
    if _MESH is None:
        raise RuntimeError(
            "model parallel mesh is not initialized "
            "(call parallel_state.initialize_model_parallel)"
        )
    return _MESH


def tp_submesh(tp: int, *, replica: int = 0, devices=None) -> Mesh:
    """A single-axis ``(TENSOR_AXIS,)`` mesh of ``tp`` devices — the
    per-replica slice a TP serving engine shard_maps over.

    Resolution order mirrors the fleet's DP×TP topology (replica ``i``
    owns TP group ``i``):

    - explicit ``devices``: use them verbatim (must be exactly ``tp``);
    - an initialized global mesh: row ``replica`` of its
      ``(dp, tensor)`` reshape — the engine inherits the training
      mesh's placement, so weights sharded by ``tensor_parallel``
      layers land where serving reads them;
    - otherwise: ``jax.devices()[replica*tp : (replica+1)*tp]``.
    """
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if devices is not None:
        devices = list(devices)
        if len(devices) != tp:
            raise ValueError(
                f"got {len(devices)} devices for tp={tp}")
        return Mesh(np.asarray(devices), (TENSOR_AXIS,))
    if _MESH is not None:
        flat = _MESH.devices.reshape(-1)
        if tp * (replica + 1) > flat.size:
            raise ValueError(
                f"replica {replica} x tp={tp} exceeds the initialized "
                f"mesh ({flat.size} devices)")
        if _TENSOR_MODEL_PARALLEL_WORLD_SIZE not in (None, 1, tp):
            raise ValueError(
                f"engine tp={tp} disagrees with the initialized mesh's "
                f"tensor axis ({_TENSOR_MODEL_PARALLEL_WORLD_SIZE})")
        group = flat[replica * tp:(replica + 1) * tp]
        return Mesh(group, (TENSOR_AXIS,))
    devs = jax.devices()
    if tp * (replica + 1) > len(devs):
        raise ValueError(
            f"replica {replica} x tp={tp} needs device "
            f"{tp * (replica + 1) - 1} but only {len(devs)} exist")
    return Mesh(np.asarray(devs[replica * tp:(replica + 1) * tp]),
                (TENSOR_AXIS,))


def axis_size(axis_name: str) -> int:
    """Static size of a named mesh axis, from inside a traced program."""
    return jax.lax.axis_size(axis_name)


def _axis_index_or_raise(axis: str, what: str):
    """Traced axis index inside shard_map; 0 if the axis has size 1."""
    sizes = {
        TENSOR_AXIS: _TENSOR_MODEL_PARALLEL_WORLD_SIZE,
        PIPELINE_AXIS: _PIPELINE_MODEL_PARALLEL_WORLD_SIZE,
        DATA_AXIS: _DATA_PARALLEL_WORLD_SIZE,
    }
    size = sizes[axis]
    if size == 1 or size is None:
        return 0
    try:
        return jax.lax.axis_index(axis)
    except NameError as e:
        raise RuntimeError(
            f"{what} is only defined inside a shard_map/pjit region binding "
            f"axis {axis!r}; a single SPMD controller has no global "
            "'current rank'"
        ) from e


# --- world sizes (reference :488-528) ---------------------------------------

def get_tensor_model_parallel_world_size() -> int:
    if _TENSOR_MODEL_PARALLEL_WORLD_SIZE is None:
        raise RuntimeError("model parallel is not initialized")
    return _TENSOR_MODEL_PARALLEL_WORLD_SIZE


def get_pipeline_model_parallel_world_size() -> int:
    if _PIPELINE_MODEL_PARALLEL_WORLD_SIZE is None:
        raise RuntimeError("model parallel is not initialized")
    return _PIPELINE_MODEL_PARALLEL_WORLD_SIZE


def get_data_parallel_world_size() -> int:
    if _DATA_PARALLEL_WORLD_SIZE is None:
        raise RuntimeError("model parallel is not initialized")
    return _DATA_PARALLEL_WORLD_SIZE


# --- ranks (reference :535-560) ---------------------------------------------

def get_tensor_model_parallel_rank():
    return _axis_index_or_raise(TENSOR_AXIS, "tensor model parallel rank")


def get_pipeline_model_parallel_rank():
    return _axis_index_or_raise(PIPELINE_AXIS, "pipeline model parallel rank")


def get_data_parallel_rank():
    return _axis_index_or_raise(DATA_AXIS, "data parallel rank")


def get_tensor_model_parallel_src_rank() -> int:
    """First rank in the current TP group (reference ``:713-718``): with a
    mesh this is always tp index 0."""
    return 0


# --- pipeline stage predicates (reference :562-640) --------------------------

def is_pipeline_first_stage(ignore_virtual: bool = False):
    if not ignore_virtual:
        vpp = _VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE
        if vpp is not None and _VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK != 0:
            return False
    return get_pipeline_model_parallel_rank() == 0


def is_pipeline_last_stage(ignore_virtual: bool = False):
    if not ignore_virtual:
        vpp = _VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE
        if vpp is not None and _VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK != vpp - 1:
            return False
    return (
        get_pipeline_model_parallel_rank()
        == get_pipeline_model_parallel_world_size() - 1
    )


def is_pipeline_stage_before_split(rank=None):
    """Reference ``:600-613`` (encoder side of an encoder-decoder split)."""
    if get_pipeline_model_parallel_world_size() == 1:
        return True
    if rank is None:
        rank = get_pipeline_model_parallel_rank()
    if _PIPELINE_MODEL_PARALLEL_SPLIT_RANK is None:
        return True
    return rank < _PIPELINE_MODEL_PARALLEL_SPLIT_RANK


def is_pipeline_stage_after_split(rank=None):
    """Reference ``:616-629``."""
    if get_pipeline_model_parallel_world_size() == 1:
        return True
    if rank is None:
        rank = get_pipeline_model_parallel_rank()
    if _PIPELINE_MODEL_PARALLEL_SPLIT_RANK is None:
        return True
    return rank >= _PIPELINE_MODEL_PARALLEL_SPLIT_RANK


def is_pipeline_stage_at_split():
    """Reference ``:632-640``."""
    rank = get_pipeline_model_parallel_rank()
    return is_pipeline_stage_before_split(rank) and is_pipeline_stage_after_split(
        rank + 1
    )


# --- virtual pipeline state (reference :643-667) -----------------------------

def get_virtual_pipeline_model_parallel_rank() -> Optional[int]:
    return _VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK


def set_virtual_pipeline_model_parallel_rank(rank: Optional[int]) -> None:
    global _VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK
    _VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK = rank


def get_virtual_pipeline_model_parallel_world_size() -> Optional[int]:
    return _VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE


def set_virtual_pipeline_model_parallel_world_size(size: Optional[int]) -> None:
    global _VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE
    _VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE = size


def get_pipeline_model_parallel_split_rank() -> Optional[int]:
    return _PIPELINE_MODEL_PARALLEL_SPLIT_RANK


def set_pipeline_model_parallel_split_rank(rank: Optional[int]) -> None:
    global _PIPELINE_MODEL_PARALLEL_SPLIT_RANK
    _PIPELINE_MODEL_PARALLEL_SPLIT_RANK = rank


# --- pipeline neighbours (reference :730-745) --------------------------------

def get_pipeline_model_parallel_next_rank():
    rank = get_pipeline_model_parallel_rank()
    return (rank + 1) % get_pipeline_model_parallel_world_size()


def get_pipeline_model_parallel_prev_rank():
    rank = get_pipeline_model_parallel_rank()
    return (rank - 1) % get_pipeline_model_parallel_world_size()


# --- embedding groups (reference :319-407,:466-486) --------------------------
# In the reference, first and last pipeline stages form an "embedding group"
# for tying input/output embeddings (plus the split stage for
# encoder-decoder models); the grad sync is an all-reduce between those
# stage ranks. On a mesh this is a predicate + masked psum over the pipeline
# axis — implemented by ``pipeline_parallel.utils.sync_embedding_grads`` /
# ``sync_position_embedding_grads``.

def is_rank_in_embedding_group(ignore_virtual: bool = False):
    """Reference ``:352-367,:466-476``: ranks [first, last] plus the
    pipeline split rank when one is set (encoder-decoder tying)."""
    in_group = is_pipeline_first_stage(ignore_virtual) | is_pipeline_last_stage(
        ignore_virtual
    )
    split = _PIPELINE_MODEL_PARALLEL_SPLIT_RANK
    if split is not None:
        in_group = in_group | (get_pipeline_model_parallel_rank() == split)
    return in_group


def is_rank_in_position_embedding_group():
    """Reference ``:354,:369-375,:479-486``: rank 0 plus the pipeline split
    rank when one is set."""
    in_group = is_pipeline_first_stage(ignore_virtual=True)
    split = _PIPELINE_MODEL_PARALLEL_SPLIT_RANK
    if split is not None:
        in_group = in_group | (get_pipeline_model_parallel_rank() == split)
    return in_group


# --- amax reduction group (fp8, reference :280-292,:472-476) -----------------
# The reference builds the amax group over tp x dp ranks within one pipeline
# stage ("Build the amax-reduction groups for fp8 precision conversion",
# parallel_state.py:280). On a mesh the group IS the (data, tensor) axis
# pair; the all-reduce is a pmax over those axes (amax = max |x| must agree
# across ranks holding shards of the same tensor before a shared fp8 scale
# is derived from it).

def get_amax_reduction_group():
    """The mesh-axis tuple the fp8 amax all-reduce runs over (reference
    ``get_amax_reduction_group``, ``parallel_state.py:472-476``). Raises
    unless ``initialize_model_parallel(..., use_fp8_=True)``, mirroring the
    reference's assert."""
    if _MESH is None:
        raise RuntimeError("model parallel is not initialized")
    if not _USE_FP8:
        raise RuntimeError(
            "amax reduction group is not initialized "
            "(initialize_model_parallel(..., use_fp8_=True))"
        )
    return (DATA_AXIS, TENSOR_AXIS)


def reduce_amax(amax, axes=None):
    """All-reduce an amax statistic over the amax-reduction group (pmax —
    ranks sharing a tensor's shards must agree on the scale they derive).
    Inside ``shard_map`` only; ``axes`` overrides the group (e.g. a subset
    when one axis is not bound)."""
    a = axes if axes is not None else get_amax_reduction_group()
    return jax.lax.pmax(amax, a)


# --- misc sizes --------------------------------------------------------------

def get_num_layers(
    num_layers: int,
    is_encoder_and_decoder_model: bool = False,
    rank: Optional[int] = None,
) -> int:
    """Layers owned by pipeline stage ``rank`` (reference ``:670-706``).

    ``rank`` defaults to the current stage, which requires a host-static
    rank — pass it explicitly during host-side model building (the builder
    iterates stages). Encoder stages (rank < split) divide the layer count by
    the encoder stage count, decoder stages by the decoder stage count,
    matching the reference's ``is_pipeline_stage_before_split`` branching.
    """
    pp = get_pipeline_model_parallel_world_size()
    if is_encoder_and_decoder_model:
        split = _PIPELINE_MODEL_PARALLEL_SPLIT_RANK
        if split is None:
            raise RuntimeError("split rank required for encoder-decoder models")
        if rank is None:
            rank = get_pipeline_model_parallel_rank()
        num_ranks_in_encoder = split
        num_ranks_in_decoder = pp - split
        if rank < split:
            return num_layers // max(num_ranks_in_encoder, 1)
        return num_layers // max(num_ranks_in_decoder, 1)
    if num_layers % pp != 0:
        raise RuntimeError(
            f"num_layers ({num_layers}) must be divisible by pipeline size ({pp})"
        )
    return num_layers // pp


def destroy_model_parallel() -> None:
    """Reference ``parallel_state.py:761-796``."""
    global _MESH, _TENSOR_MODEL_PARALLEL_WORLD_SIZE
    global _PIPELINE_MODEL_PARALLEL_WORLD_SIZE, _DATA_PARALLEL_WORLD_SIZE
    global _VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK
    global _VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE
    global _PIPELINE_MODEL_PARALLEL_SPLIT_RANK
    global _USE_FP8
    _MESH = None
    _TENSOR_MODEL_PARALLEL_WORLD_SIZE = None
    _PIPELINE_MODEL_PARALLEL_WORLD_SIZE = None
    _DATA_PARALLEL_WORLD_SIZE = None
    _VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK = None
    _VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE = None
    _PIPELINE_MODEL_PARALLEL_SPLIT_RANK = None
    _USE_FP8 = False
