"""``flax`` and ``optax`` stay off the trainers' import path.

Importing them costs about half a second each on the v5e host, inside
every run's ``setup_s``; a train step needs neither (``apex_tpu/_lazy.py``).
The flax module classes and the O1 cast lists load at their first lookup.
"""
import subprocess
import sys

import pytest

_TRAIN_PATH = """
import sys
import apex_tpu
from apex_tpu import amp
from apex_tpu.amp import LossScaler
from apex_tpu.optimizers import FusedAdam, FusedLAMB, FusedSGD
from apex_tpu.parallel import DistributedDataParallel, GradBuckets
from apex_tpu.transformer.testing import GPTConfig, bert_forward, gpt_loss
print(",".join(m for m in ("flax", "optax") if m in sys.modules))
"""


def test_train_path_imports_neither_flax_nor_optax():
    """What ``benchmark/train_cell.py``'s three recipes import."""
    out = subprocess.run(
        [sys.executable, "-c", _TRAIN_PATH], capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "PYTHONPATH": ":".join(sys.path)}, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "", f"imported: {out.stdout.strip()}"


@pytest.mark.parametrize("module, name", [
    ("apex_tpu.parallel", "SyncBatchNorm"),
    ("apex_tpu.parallel", "convert_syncbn_model"),
    ("apex_tpu.parallel.sync_batchnorm", "SyncBatchNorm"),
    ("apex_tpu.transformer.tensor_parallel", "ColumnParallelLinear"),
    ("apex_tpu.transformer.tensor_parallel", "RowParallelLinear"),
    ("apex_tpu.transformer.tensor_parallel.layers", "VocabParallelEmbedding"),
    ("apex_tpu.normalization", "FusedLayerNorm"),
])
def test_flax_modules_load_on_first_use(module, name):
    import importlib

    import flax.linen as nn

    cls = getattr(importlib.import_module(module), name)
    assert callable(cls)
    if isinstance(cls, type):
        assert issubclass(cls, nn.Module)
    with pytest.raises(AttributeError):
        getattr(importlib.import_module(module), "NoSuchModule")


def test_cast_lists_build_on_first_use():
    """The O1 lists keep their optax and apex_tpu entries, built when an
    ``autocast`` (or a reader) first asks for them."""
    import optax

    import apex_tpu
    from apex_tpu.amp.lists import jax_overrides as jo

    low, fp32 = jo.LOW_PRECISION_FUNCS, jo.FP32_FUNCS
    assert low is jo.LOW_PRECISION_FUNCS  # built once
    assert (apex_tpu.mlp, "mlp") in low
    assert (optax, "softmax_cross_entropy") in fp32
    assert (apex_tpu.contrib.xentropy, "softmax_cross_entropy_loss") in fp32
    import jax.numpy as jnp
    assert (jnp, "matmul") in low and (jnp, "exp") in fp32
    with pytest.raises(AttributeError):
        jo.NO_SUCH_LIST


_DENSE_PATH = _TRAIN_PATH.replace(
    'print(",".join(m for m in ("flax", "optax") if m in sys.modules))',
    'print(",".join(m for m in ("apex_tpu.transformer.moe", '
    '"apex_tpu.ops.grouped_matmul", "apex_tpu.ops.moe_rows") '
    'if m in sys.modules))')


def test_the_expert_layer_loads_on_first_use():
    """The dense cells' imports load neither the expert layer nor its
    kernels; a stack with an expert layer loads all three when traced."""
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "PYTHONPATH": ":".join(sys.path)}
    out = subprocess.run([sys.executable, "-c", _DENSE_PATH],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "", f"imported: {out.stdout.strip()}"
    first_use = _DENSE_PATH.replace("print(", """
import jax, jax.numpy as jnp
from apex_tpu.transformer.testing import LayerKind, init_gpt_params
cfg = GPTConfig(num_layers=1, hidden_size=32, num_attention_heads=2,
                vocab_size=64, hidden_dropout=0.0, attention_dropout=0.0,
                layer_kinds=(LayerKind(None, False, True),), gated_mlp=True,
                num_experts=4, experts_held=(0, 2), experts_per_token=2,
                expert_ffn_size=16)
tokens = jnp.zeros((1, 8), jnp.int32)
jax.eval_shape(lambda p: gpt_loss(cfg, p, tokens, tokens),
               init_gpt_params(cfg, jax.random.PRNGKey(0)))
print(""", 1)
    out = subprocess.run([sys.executable, "-c", first_use],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == (
        "apex_tpu.transformer.moe,apex_tpu.ops.grouped_matmul,"
        "apex_tpu.ops.moe_rows")
