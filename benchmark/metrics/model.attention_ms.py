"""Device time per step under ``apex_tpu.attention`` in all phases: the
qkv GEMM, the layout changes around the flash kernel, the kernel (it holds
``kernels.flash_attention_ms``) and the out projection."""
from benchmark import scope_reduce as sr


def read(run):
    t = sr.table_of(run)
    return None if t is None else sr.total(t, layers=(sr.ATTENTION,))
