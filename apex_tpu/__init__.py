"""apex_tpu: a TPU-native training-acceleration framework.

A ground-up JAX/XLA/Pallas re-design of the capability surface of NVIDIA Apex
(reference: jindajia/apex; see SURVEY.md). Nothing here is a translation of the
CUDA implementation: kernels are Pallas/XLA, collectives are `jax.lax` psum /
all_gather / psum_scatter / ppermute over a `jax.sharding.Mesh`, and mixed
precision is a functional autocast policy plus a dynamic loss scaler rather than
module monkey-patching.

Public subpackages (mirroring the reference's ``apex/__init__.py:31-68`` lazy
import surface):

- ``apex_tpu.amp``               mixed precision (O0-O3, loss scaling)
- ``apex_tpu.optimizers``        fused multi-tensor optimizers
- ``apex_tpu.normalization``     fused LayerNorm / RMSNorm
- ``apex_tpu.parallel``          data parallel (grad sync, SyncBN, LARC)
- ``apex_tpu.transformer``       Megatron-style TP/PP/SP transformer library
- ``apex_tpu.contrib``           production kernel pack (ZeRO optimizers, flash
                                 attention, xentropy, group norm, ASP, ...)
- ``apex_tpu.fp16_utils``        legacy manual mixed-precision utilities
- ``apex_tpu.mlp`` / ``apex_tpu.fused_dense``  fused MLP / dense modules
- ``apex_tpu.telemetry``         training-run observability (in-jit metrics,
                                 JSONL/ring sinks, trace sessions, pipeline
                                 bubble accounting)
- ``apex_tpu.resilience``        fault tolerance (preemption-safe async
                                 checkpointing, last-good rewind, hang
                                 watchdog, fault-injection harness)
"""
import logging
import sys

__version__ = "0.1.0"


class RankInfoFormatter(logging.Formatter):
    """Log formatter prefixing each record with the JAX process index.

    TPU-native analogue of the reference's rank-aware formatter
    (``apex/__init__.py:31-43``): instead of torch.distributed rank we report
    ``jax.process_index()/jax.process_count()``, resolved lazily so importing
    apex_tpu never forces backend initialisation.
    """

    def format(self, record):
        try:
            import jax

            rank_info = f"[{jax.process_index()}/{jax.process_count()}]"
        except Exception:  # backend not initialised yet
            rank_info = "[-/-]"
        record.rank_info = rank_info
        return super().format(record)


_library_root_logger = logging.getLogger(__name__)


def _setup_logger() -> None:
    handler = logging.StreamHandler(sys.stderr)
    handler.setFormatter(
        RankInfoFormatter(
            "%(asctime)s - %(name)s - %(levelname)s - %(rank_info)s - %(message)s"
        )
    )
    _library_root_logger.addHandler(handler)
    _library_root_logger.propagate = False


_setup_logger()


def set_logging_level(level) -> None:
    """Set the apex_tpu library logging level (reference ``apex/__init__.py:60``)."""
    _library_root_logger.setLevel(level)


# Eager, lightweight subpackages. Heavy ones (transformer, contrib, and
# normalization, whose module classes import flax) are imported lazily via
# __getattr__ to keep `import apex_tpu` cheap.
from . import amp  # noqa: F401,E402
from . import optimizers  # noqa: F401,E402
from . import multi_tensor_apply  # noqa: F401,E402

_LAZY_SUBMODULES = (
    "analysis",
    "normalization",
    "parallel",
    "transformer",
    "contrib",
    "fp16_utils",
    "mlp",
    "fused_dense",
    "ops",
    "RNN",
    "checkpoint",
    "telemetry",
    "resilience",
)


def __getattr__(name):
    if name in _LAZY_SUBMODULES:
        import importlib

        try:
            module = importlib.import_module(f".{name}", __name__)
        except ModuleNotFoundError as e:
            raise AttributeError(
                f"module {__name__!r} has no attribute {name!r}"
            ) from e
        globals()[name] = module
        return module
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_LAZY_SUBMODULES))
