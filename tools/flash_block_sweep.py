"""Flash-attention block-size sweep across shapes and layouts.

Measures the flash kernels' device times from a profiler trace for a list
of ``(batch, heads, seq, head_dim)`` shapes over candidate ``(block_q,
block_k)`` tilings, in the head-major layout (``flash_attention``, ``[b,
n, s, d]``) and the batch-major one (``flash_attention_bshd``, ``[b, s,
n, d]``: two heads of 64 side by side in a 128-lane block), differentiating
w.r.t. q, k AND v with all cotangents consumed — differentiating w.r.t. q
alone lets XLA dead-code-eliminate the dkv kernel and reports a fantasy bwd
time (the round-5 regression this file exists to prevent).

Run on a real TPU:  python tools/flash_block_sweep.py [dense|long]
Prints one line per (shape, layout, tiling): the three kernels' times and
everything else the step ran on the device (XLA's own copies and
transposes round the kernels), ms per forward + backward pass. The table
is in ``docs/flash_block_sweep.md``; ``_bwd_block_table`` holds what it
concluded.
"""
import glob
import sys
import tempfile
from collections import defaultdict

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")

from apex_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention,
    flash_attention_bshd,
)

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


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "dense"
    for b, n, s, d in SHAPES[which]:
        for layout, fn, shape in (
                ("bnsd", flash_attention, (b, n, s, d)),
                ("bshd", flash_attention_bshd, (b, s, n, d))):
            for causal in (True, False):
                for bq, bk in CAND:
                    if bq > s or bk > s:
                        continue
                    tag = (f"b={b} n={n} s={s} d={d} {layout} "
                           f"causal={int(causal)} bq={bq} bk={bk}")
                    try:
                        t = measure(fn, shape, causal, bq, bk)
                    except Exception as e:  # e.g. VMEM past the scoped limit
                        msg = str(e).splitlines()[0][:90] if str(e) else ""
                        print(f"{tag} FAILED: {type(e).__name__} {msg}",
                              flush=True)
                        continue
                    flash = sum(t.get(k, 0.0) for k in KERNELS)
                    parts = " ".join(
                        f"{k.replace('apex_tpu_flash_', '')}={t.get(k, 0.0):.3f}"
                        for k in KERNELS)
                    print(f"{tag} flash={flash:.3f} ms ({parts}) "
                          f"other={t.get('other', 0.0):.3f}", flush=True)


if __name__ == "__main__":
    main()
