"""Flax modules over the functional tensor-parallel cores of
``layers.py`` (shard_map-resident: params are local shards). Reached as
``apex_tpu.transformer.tensor_parallel.ColumnParallelLinear`` etc.; apart
from the cores so that importing them does not import flax."""
from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax.numpy as jnp

from .. import parallel_state
from .layers import (
    column_parallel_linear,
    init_affine_weight_shard,
    row_parallel_linear,
    vocab_parallel_embedding,
)
from .utils import divide


class ColumnParallelLinear(nn.Module):
    """Flax module over :func:`column_parallel_linear`
    (reference class ``layers.py:460-643``); returns the core's
    ``(out, out_bias, new_fp8_state)`` 3-tuple (fp8 slot ``None``
    here — the module runs the plain GEMM path)."""

    input_size: int
    output_size: int
    bias: bool = True
    gather_output: bool = True
    init_method: Callable = nn.initializers.lecun_normal()
    skip_bias_add: bool = False
    sequence_parallel_enabled: bool = False
    gradient_accumulation_fusion: bool = False
    params_dtype: Any = jnp.float32
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        tp = parallel_state.get_tensor_model_parallel_world_size()
        out_local = divide(self.output_size, tp)
        weight = self.param(
            "weight",
            lambda k, s, d: init_affine_weight_shard(
                k, self.init_method, s, self.axis_name, d
            ),
            (out_local, self.input_size),
            self.params_dtype,
        )
        b = (
            self.param(
                "bias", nn.initializers.zeros, (out_local,), self.params_dtype
            )
            if self.bias
            else None
        )
        return column_parallel_linear(
            x, weight, b,
            axis_name=self.axis_name,
            gather_output=self.gather_output,
            sequence_parallel_enabled=self.sequence_parallel_enabled,
            skip_bias_add=self.skip_bias_add,
            gradient_accumulation_fusion=self.gradient_accumulation_fusion,
        )


class RowParallelLinear(nn.Module):
    """Flax module over :func:`row_parallel_linear`
    (reference class ``layers.py:645-750``); returns the core's
    ``(out, out_bias, new_fp8_state)`` 3-tuple (fp8 slot ``None``
    here — the module runs the plain GEMM path)."""

    input_size: int
    output_size: int
    bias: bool = True
    input_is_parallel: bool = False
    init_method: Callable = nn.initializers.lecun_normal()
    skip_bias_add: bool = False
    sequence_parallel_enabled: bool = False
    gradient_accumulation_fusion: bool = False
    params_dtype: Any = jnp.float32
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        tp = parallel_state.get_tensor_model_parallel_world_size()
        in_local = divide(self.input_size, tp)
        weight = self.param(
            "weight",
            lambda k, s, d: init_affine_weight_shard(
                k, self.init_method, s, self.axis_name, d
            ),
            (self.output_size, in_local),
            self.params_dtype,
        )
        b = (
            self.param(
                "bias", nn.initializers.zeros, (self.output_size,),
                self.params_dtype,
            )
            if self.bias
            else None
        )
        return row_parallel_linear(
            x, weight, b,
            axis_name=self.axis_name,
            input_is_parallel=self.input_is_parallel,
            sequence_parallel_enabled=self.sequence_parallel_enabled,
            skip_bias_add=self.skip_bias_add,
            gradient_accumulation_fusion=self.gradient_accumulation_fusion,
        )


class VocabParallelEmbedding(nn.Module):
    """Flax module over :func:`vocab_parallel_embedding`
    (reference class ``layers.py:174-255``)."""

    num_embeddings: int
    embedding_dim: int
    init_method: Callable = nn.initializers.normal(stddev=1.0)
    params_dtype: Any = jnp.float32
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, ids):
        tp = parallel_state.get_tensor_model_parallel_world_size()
        vocab_local = divide(self.num_embeddings, tp)
        weight = self.param(
            "weight",
            lambda k, s, d: init_affine_weight_shard(
                k, self.init_method, s, self.axis_name, d
            ),
            (vocab_local, self.embedding_dim),
            self.params_dtype,
        )
        return vocab_parallel_embedding(ids, weight, axis_name=self.axis_name)
