"""Flash-attention block-size sweep across shapes and layouts.

Measures the flash kernels' device times from a profiler trace for a list
of ``(batch, heads, seq, head_dim)`` shapes over candidate ``(block_q,
block_k)`` tilings, in the head-major layout (``flash_attention``, ``[b,
n, s, d]``) and the batch-major one (``flash_attention_bshd``, ``[b, s,
n, d]``: two heads of 64 side by side in a 128-lane block), differentiating
w.r.t. q, k AND v with all cotangents consumed — differentiating w.r.t. q
alone lets XLA dead-code-eliminate the dkv kernel and reports a fantasy bwd
time (the round-5 regression this file exists to prevent).

Run on a real TPU:  python tools/flash_block_sweep.py [dense|long|walk]
Prints one line per (shape, layout, tiling): the three kernels' times and
everything else the step ran on the device (XLA's own copies and
transposes round the kernels), ms per forward + backward pass, and the
share of the score square the kernels compute (``dense_walk_share``).
``walk`` holds the tile whole (one tile a sequence) and tries the rows
of a chunk of the triangular walk of a causal square (``WALK``: the
module's rule ``_chunk_rows`` is stood in for, as no argument sets it).
The tables are in ``docs/flash_block_sweep.md``; ``_bwd_block_table`` and
``_chunk_rows`` hold what they concluded.
"""
import glob
import importlib
import sys
import tempfile
from collections import defaultdict
from unittest import mock

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")

fa = importlib.import_module("apex_tpu.ops.flash_attention")

REPS = 8
KERNELS = ("apex_tpu_flash_fwd", "apex_tpu_flash_bwd_dkv",
           "apex_tpu_flash_bwd_dq")
SHAPES = {
    # the dense cells' attention: 16 heads of 64, 8 x 1024 and 16 x 512
    "dense": [(8, 16, 1024, 64), (16, 16, 512, 64)],
    # b*s = 32k tokens, 8 heads (the table of the head-major kernels)
    "long": [(32, 8, 512, 64), (16, 8, 1024, 64), (8, 8, 2048, 64),
             (4, 8, 4096, 64), (32, 8, 512, 128), (16, 8, 1024, 128),
             (8, 8, 2048, 128), (4, 8, 4096, 128)],
}
CAND = [(1024, 1024), (1024, 512), (512, 1024), (512, 512), (256, 256),
        (2048, 2048)]
WALK = [0, 512, 256, 128]       # rows of a chunk; 0: the square whole


def device_ms(trace_dir):
    """ms per repetition on the first TPU's ``XLA Ops`` line: each flash
    kernel by name, and ``other`` (every other operation of the step)."""
    from jax.profiler import ProfileData

    per = defaultdict(float)
    path = sorted(glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True))[-1]
    plane = next(p for p in ProfileData.from_file(path).planes
                 if p.name.startswith("/device:TPU:0"))
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            name = next((k for k in KERNELS if k in ev.name), "other")
            per[name] += ev.duration_ns
    return {k: v / 1e6 / REPS for k, v in per.items()}


def measure(fn, shape, causal, bq, bk):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in ks)

    def loss(qq, kk, vv):
        o = fn(qq, kk, vv, causal=causal, block_q=bq, block_k=bk,
               bwd_block_q=bq, bwd_block_k=bk)
        return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32))

    g = jax.grad(loss, argnums=(0, 1, 2))

    @jax.jit
    def step(x):
        dq, dk, dv = g(x, k, v)
        # consume every cotangent so nothing is DCE'd
        return (dq + dk + dv).astype(jnp.bfloat16) * 1e-6 + q

    x = q
    for _ in range(2):
        x = step(x)
    jax.block_until_ready(x)
    trace_dir = tempfile.mkdtemp(prefix="fbs_")
    with jax.profiler.trace(trace_dir):
        for _ in range(REPS):
            x = step(x)
        jax.block_until_ready(x)
    return device_ms(trace_dir)


def line(fn, shape, s, causal, bq, bk, tag):
    try:
        t = measure(fn, shape, causal, bq, bk)
    except Exception as e:  # e.g. VMEM past the scoped limit
        msg = str(e).splitlines()[0][:90] if str(e) else ""
        print(f"{tag} FAILED: {type(e).__name__} {msg}", flush=True)
        return
    share = fa.dense_walk_share(s, s, bq, bk, causal)
    flash = sum(t.get(k, 0.0) for k in KERNELS)
    parts = " ".join(
        f"{k.replace('apex_tpu_flash_', '')}={t.get(k, 0.0):.3f}"
        for k in KERNELS)
    print(f"{tag} walk={share:.4f} flash={flash:.3f} ms ({parts}) "
          f"other={t.get('other', 0.0):.3f}", flush=True)


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "dense"
    for b, n, s, d in SHAPES["dense" if which == "walk" else which]:
        for layout, fn, shape in (
                ("bnsd", fa.flash_attention, (b, n, s, d)),
                ("bshd", fa.flash_attention_bshd, (b, s, n, d))):
            for causal in (True, False):
                if which == "walk":
                    # one tile a sequence; causal: each chunk size tried
                    for c in WALK if causal else [0]:
                        if c >= s:
                            continue
                        rule = lambda bq, c=c: c or bq  # noqa: E731
                        with mock.patch.object(fa, "_chunk_rows", rule):
                            line(fn, shape, s, causal, s, s,
                                 f"b={b} n={n} s={s} d={d} {layout} "
                                 f"causal={int(causal)} bq={s} bk={s} "
                                 f"c={c or 'square'}")
                    continue
                for bq, bk in CAND:
                    if bq > s or bk > s:
                        continue
                    line(fn, shape, s, causal, bq, bk,
                         f"b={b} n={n} s={s} d={d} {layout} "
                         f"causal={int(causal)} bq={bq} bk={bk}")


if __name__ == "__main__":
    main()
