"""Fake-host training program for the elastic supervisor.

The PR 5 crash-harness subprocess (``tests/_resilience_train.py``)
promoted from test fixture to product: one *fake host* of a supervised
world. Each host runs the full bucketed flat-gradient lifecycle
(``GradBuckets`` packing, ``LossScaler.unscale_flat``, packed
``FusedAdam`` with fp32 masters) over a fixed global batch stream —
compute is replicated, the checkpoint is SHARDED: host ``h`` writes
rows ``spec.shard_bounds(world)[h]`` of every flat buffer through the
two-phase :class:`~apex_tpu.resilience.elastic.ElasticCheckpointManager`
commit, heartbeats every step for the supervisor's hang detector, and
auto-resumes from the newest *committed* step on launch — including
onto a different world size than the checkpoint was saved from
(topology-elastic resume re-flattens the packed state bit-exactly).

Because the global batch is world-invariant, the per-step loss records
(``S <step> <f32.hex()>`` appended by host 0) are byte-identical across
any kill/restart/reshape history — the oracle every chaos test holds
the service to.

Driven by ``tools/elastic_supervisor.py``, ``tests/test_elastic.py``
and the ``host_kill`` leg of ``tools/resilience_check.py --self``.
Chaos faults arrive as a :meth:`ChaosHost.parse` spec via ``--chaos``
or the ``APEX_TPU_ELASTIC_CHAOS`` environment variable (the
supervisor's per-incarnation arming channel).

Exit codes: 0 = reached ``--steps``; killed hosts die by SIGKILL (no
code of their own); 17 = preempted (SIGTERM emergency flush, mirroring
``_resilience_train.py``).
"""
import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp

# PRNG determinism across harnesses: the pytest conftest flips
# jax_threefry_partitionable (for its 8-virtual-device mesh), which
# changes every jax.random draw. Pin it HERE — the module both the
# subprocess fake hosts and the in-process reference runs
# (resilience_check legs, bench, tests) import — so supervised worlds
# and their oracles draw the same random streams no matter which
# harness launched them.
jax.config.update("jax_threefry_partitionable", True)

from apex_tpu.amp.scaler import LossScaler  # noqa: E402
from apex_tpu.optimizers import FusedAdam  # noqa: E402
from apex_tpu.resilience import (  # noqa: E402
    ChaosHost,
    ElasticCheckpointManager,
    Heartbeat,
    HangWatchdog,
    IndexedBatches,
    capture,
    grad_buckets_for_world,
    resume_or_init,
)
from apex_tpu.telemetry import JsonlRecorder, TaggedRecorder  # noqa: E402

N_IN, HID, BATCH = 8, 16, 4


def batch_fn(i):
    """The GLOBAL batch for step-index ``i`` — identical on every host
    and at every world size, so the training math is world-invariant
    and loss records are byte-comparable across reshapes."""
    k = jax.random.fold_in(jax.random.PRNGKey(1234), i)
    kx, ky = jax.random.split(k)
    x = jax.random.normal(kx, (BATCH, N_IN), jnp.float32)
    y = (jnp.sum(x, axis=1, keepdims=True)
         + 0.1 * jax.random.normal(ky, (BATCH, 1)))
    return x, y


def init_params():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return {
        "b1": jnp.zeros((HID,), jnp.float32),
        "w1": 0.3 * jax.random.normal(k1, (N_IN, HID), jnp.float32),
        "w2": 0.3 * jax.random.normal(k2, (HID, 1), jnp.float32),
    }


def build_world(world: int, *, chunk: int = 256,
                bucket_cap_mb: float = 0.005):
    """(buckets, opt, scaler) for ``world`` — the world-parameterized
    layout every host of an incarnation shares."""
    params = init_params()
    buckets = grad_buckets_for_world(
        params, world, bucket_cap_mb=bucket_cap_mb, chunk_size=chunk)
    opt = FusedAdam(lr=1e-2, packed=True, packed_spec=buckets.spec,
                    master_weights=True)
    sc = LossScaler("dynamic", init_scale=2.0 ** 8, scale_window=5)
    return params, buckets, opt, sc


def make_train_step(buckets, opt, sc):
    """The jitted step every fake host runs — also imported by
    ``tools/resilience_check.py`` and the tests as the REFERENCE
    (in-process, uninterrupted) oracle, so the byte-identity proofs
    compare against the literal same computation."""

    @jax.jit
    def train_step(params, opt_state, sstate, rng, x, y):
        rng, sub = jax.random.split(rng)

        def loss_fn(p):
            h = jnp.tanh(x @ p["w1"] + p["b1"])
            keep = jax.random.bernoulli(sub, 0.9, h.shape)
            h = jnp.where(keep, h, 0.0)
            pred = h @ p["w2"]
            return jnp.mean((pred - y) ** 2)

        def scaled(p):
            loss = loss_fn(p)
            return sc.scale_loss(sstate, loss), loss

        (_, loss), grads = jax.value_and_grad(
            scaled, has_aux=True)(params)
        flat = buckets.concat(buckets.pack(grads))
        flat, new_ss = sc.unscale_flat(sstate, flat,
                                       out_dtype=jnp.float32)
        params, opt_state = opt.step(
            flat, opt_state, params, found_inf=new_ss.found_inf)
        return params, opt_state, sc.update_scale(new_ss), rng, loss

    return train_step


def reference_records(world: int, steps: int, *, start_state=None):
    """Loss records ``{step: f32.hex()}`` of an UNINTERRUPTED run at
    ``world``'s layout, from ``start_state`` (or step 0) to ``steps`` —
    the oracle the supervised/chaos runs must match byte-for-byte."""
    _, buckets, opt, sc = build_world(world)
    train_step = make_train_step(buckets, opt, sc)
    if start_state is None:
        params = init_params()
        opt_state, sstate = opt.init(params), sc.init_state()
        rng, done = jax.random.PRNGKey(42), 0
        pos = 0
    else:
        params, opt_state = start_state.params, start_state.opt_state
        sstate, rng = start_state.scaler, start_state.rng
        done = int(start_state.step)
        pos = int(start_state.data["position"])
    it = IndexedBatches(batch_fn, position=pos)
    records = {}
    while done < steps:
        x, y = next(it)
        params, opt_state, sstate, rng, loss = train_step(
            params, opt_state, sstate, rng, x, y)
        records[done] = float(loss).hex()
        done += 1
    final = capture(done, params, opt_state, scaler=sstate, rng=rng,
                    data=it.state())
    return records, final


def main() -> int:
    # a fake host is a CPU stand-in: a chip belongs to ONE process (the
    # supervisor's, if any), so the host must never ask for it — whatever
    # platform its environment exports
    jax.config.update("jax_platforms", "cpu")
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", type=int, required=True)
    ap.add_argument("--world", type=int, required=True)
    ap.add_argument("--steps", type=int, required=True)
    ap.add_argument("--root", required=True)
    ap.add_argument("--losses", default=None,
                    help="host 0 appends 'S <step> <loss.hex()>' lines")
    ap.add_argument("--heartbeat-dir", required=True)
    ap.add_argument("--save-every", type=int, default=3)
    ap.add_argument("--barrier-timeout", type=float, default=60.0)
    ap.add_argument("--chaos", default=None,
                    help="ChaosHost.parse spec, e.g. 'kill@7' "
                         "(or env APEX_TPU_ELASTIC_CHAOS)")
    ap.add_argument("--events", default=None,
                    help="JSONL event sink (host/rank-tagged)")
    ap.add_argument("--step-sleep", type=float, default=0.0)
    args = ap.parse_args()

    chaos_spec = args.chaos or os.environ.get("APEX_TPU_ELASTIC_CHAOS", "")
    chaos = ChaosHost.parse(chaos_spec) if chaos_spec else None

    sink = None
    if args.events:
        sink = TaggedRecorder(JsonlRecorder(args.events), owns_sink=True,
                              tags={"host": args.host, "rank": args.host})
    # the in-host watchdog: hang events from supervised hosts carry the
    # host id/rank (the TaggedRecorder mirror for hang dumps)
    watchdog = HangWatchdog(
        timeout_s=max(10.0, 2 * args.barrier_timeout), sink=sink,
        context={"host": args.host, "rank": args.host})

    hb = Heartbeat(os.path.join(args.heartbeat_dir, f"hb-{args.host}"),
                   args.host)
    params, buckets, opt, sc = build_world(args.world)
    train_step = make_train_step(buckets, opt, sc)

    def init_state():
        p = init_params()
        return capture(0, p, opt.init(p), scaler=sc.init_state(),
                       rng=jax.random.PRNGKey(42),
                       data={"position": 0})

    mgr = ElasticCheckpointManager(
        args.root, host=args.host, world=args.world,
        keep_n=2, async_save=True, save_every=args.save_every,
        sink=sink, watchdog=watchdog,
        barrier_timeout_s=args.barrier_timeout, chaos=chaos)
    state, resumed = resume_or_init(mgr, init_state)
    it = IndexedBatches(batch_fn, position=int(state.data["position"]))
    params = jax.device_put(state.params)
    opt_state = jax.device_put(state.opt_state)
    sstate = jax.device_put(state.scaler)
    rng = jax.device_put(state.rng)
    done = int(state.step)

    latest = {"state": capture(
        done, params, opt_state, scaler=sstate, rng=rng,
        data=it.state())}
    mgr.install_preemption_handler(lambda: latest["state"])

    hb.beat(done)  # first beat: init/resume finished, loop entered
    # startup rendezvous (the jax.distributed.initialize analogue):
    # wait until every peer of this incarnation has beaten once, so the
    # world steps roughly in lockstep instead of a fast host racing
    # steps ahead while a peer is still importing. Best effort — a peer
    # that never shows up is the SUPERVISOR's incident to detect, not
    # ours to die on.
    deadline = time.monotonic() + args.barrier_timeout  # det-lint: ok (startup barrier deadline, wall-domain)
    while time.monotonic() < deadline:  # det-lint: ok (startup barrier deadline, wall-domain)
        if all(os.path.exists(os.path.join(args.heartbeat_dir,
                                           f"hb-{h}"))
               for h in range(args.world)):
            break
        time.sleep(0.02)
    losses_f = open(args.losses, "a") if (args.losses
                                          and args.host == 0) else None
    try:
        while done < args.steps:
            x, y = next(it)
            params, opt_state, sstate, rng, loss = train_step(
                params, opt_state, sstate, rng, x, y)
            done += 1
            if losses_f is not None:
                losses_f.write(f"S {done - 1} {float(loss).hex()}\n")
                losses_f.flush()
            if chaos is not None:
                stall = chaos.take_wedge(done)
                if stall is not None:
                    time.sleep(stall)  # wedged: NO heartbeat
                chaos.at_step_boundary(done)
            hb.beat(done)
            latest["state"] = capture(
                done, params, opt_state, scaler=sstate, rng=rng,
                data=it.state())
            mgr.maybe_save(latest["state"])
            if mgr.preempted:
                return 17
            if args.step_sleep:
                time.sleep(args.step_sleep)
        if losses_f is not None:
            losses_f.write(f"F {done} {float(sstate.loss_scale)}\n")
            losses_f.flush()
    finally:
        if losses_f is not None:
            losses_f.close()
    mgr.close()
    watchdog.close()
    return 0


if __name__ == "__main__":
    rc = main()
    # exit without interpreter teardown (see tests/_resilience_train.py:
    # tensorstore/XLA background threads can abort during C++ static
    # teardown under load — a post-work crash that would read as failure)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
