"""Operations and bytes an algorithm needs, from shapes alone.

These are the numerators of every utilisation and roofline share the
benchmark reports. They count what the mathematics requires: recomputed
operations earn no credit, and a kernel that reads its operands twice is
charged for one read.
"""
from __future__ import annotations


def train_flops_per_step(layers: int, hidden: int, ffn: int, vocab: int,
                         batch: int, seq: int, causal: bool) -> float:
    """Matmul FLOPs of one forward + backward step (backward = 2 x
    forward). Causal attention needs half of the score matrix. (Copied from
    ``bench.train_flops_per_step``.)"""
    attn_pairs = seq * seq * (0.5 if causal else 1.0)
    per_layer = (
        2 * batch * seq * hidden * (3 * hidden)       # qkv projection
        + 2 * 2 * batch * attn_pairs * hidden         # q k^T and p v
        + 2 * batch * seq * hidden * hidden           # output projection
        + 2 * 2 * batch * seq * hidden * ffn          # fc1 + fc2
    )
    head = 2 * batch * seq * hidden * vocab
    return 3.0 * (layers * per_layer + head)


def flash_attention_step(layers: int, heads: int, head_dim: int, batch: int,
                         seq: int, causal: bool, bytes_per_el: int = 2):
    """``(flops, bytes)`` attention needs in one training step over all
    layers. Forward is two matmuls (q k^T, p v); backward needs four more
    (dv, dp, dq, dk); the score recompute inside the backward kernel and a
    forward replayed by activation recompute earn nothing. Bytes: forward
    reads q, k, v and writes o; backward reads q, k, v, o, do and writes
    dq, dk, dv (the row statistics are 1/head_dim of that and left out)."""
    pairs = seq * seq * (0.5 if causal else 1.0)
    unit = 2.0 * batch * heads * pairs * head_dim
    flops = layers * 6.0 * unit
    tensor = batch * heads * seq * head_dim * bytes_per_el
    return flops, float(layers * (4 + 8) * tensor)


def roofline_share(flops: float, nbytes: float, seconds: float, peaks):
    """``(share in percent, which roof)``: the least time the chip could
    take over the time it took. ``None`` where no time was measured."""
    if not seconds or seconds <= 0:
        return None
    t_flops = flops / peaks.flops_per_s
    t_bytes = nbytes / peaks.hbm_bytes_per_s
    roof = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, roof
