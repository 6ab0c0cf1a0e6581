"""ImageNet training with apex_tpu amp — TPU-native port of the reference
example ``/root/reference/examples/imagenet/main_amp.py``.

Covers the driver BASELINE configs:

  #1  ResNet-50, amp O2 + FusedSGD, single chip:
      python main_amp.py --arch resnet50 --opt-level O2 --synthetic
  #2  ResNet-50, DDP + SyncBatchNorm + FusedAdam over the device mesh:
      python main_amp.py --arch resnet50 --opt-level O2 --sync_bn \
          --optimizer adam --synthetic

Differences from the CUDA example, by design (cited against the reference):

- ``torch.distributed.launch`` + per-process ``local_rank`` (``main_amp.py:120-138``)
  collapse into one SPMD program over a ``jax.sharding.Mesh`` axis ``"data"``;
  DDP is the ``sync_gradients`` transform inside the jitted step instead of
  backward hooks (``apex/parallel/distributed.py:323-412``).
- ``fast_collate`` / ``data_prefetcher`` with side CUDA streams
  (``main_amp.py:28-41,198-236``) have no analogue: batches are host numpy
  arrays handed to ``jit`` (XLA pipelines the H2D copy). The synthetic-data
  path mirrors how the L1 harness measures throughput.
- ``--channels-last`` is meaningless: NHWC is the native TPU layout and the
  only one used.
- amp: ``amp.initialize(..., opt_level)`` returns cast params + scaler state
  instead of patching the model; the loss-scale skip-step runs under
  ``lax.cond`` inside the step (same semantics as ``amp.scale_loss``,
  ``apex/amp/handle.py:17-124``).

Training-loop parity kept: per-epoch train/validate, prec@1/prec@5
``AverageMeter``s, ``Speed`` img/s prints (``main_amp.py:392,458``), the
lr schedule with 5-epoch warmup and /10 decays at 30/60/80
(``adjust_learning_rate``, ``main_amp.py:470-486``), checkpoint save/resume.
"""
from __future__ import annotations

import argparse
import functools
import os
import pickle
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu import amp
from apex_tpu.optimizers import FusedAdam, FusedSGD
from apex_tpu.parallel import sync_gradients

import resnet as resnet_lib


def parse():
    parser = argparse.ArgumentParser(description="JAX/TPU ImageNet Training")
    parser.add_argument("data", nargs="?", default=None,
                        help="path to dataset (omit with --synthetic)")
    parser.add_argument("--arch", "-a", default="resnet50",
                        choices=resnet_lib.model_names())
    parser.add_argument("--epochs", default=90, type=int)
    parser.add_argument("--start-epoch", default=0, type=int)
    parser.add_argument("-b", "--batch-size", default=256, type=int,
                        help="global batch size (split across the mesh)")
    parser.add_argument("--lr", "--learning-rate", default=0.1, type=float,
                        help="initial lr, scaled by global_batch/256 with "
                             "5-epoch warmup (reference behaviour)")
    parser.add_argument("--momentum", default=0.9, type=float)
    parser.add_argument("--weight-decay", "--wd", default=1e-4, type=float)
    parser.add_argument("--print-freq", "-p", default=10, type=int)
    parser.add_argument("--resume", default="", type=str)
    parser.add_argument("--evaluate", "-e", action="store_true")
    parser.add_argument("--prof", default=-1, type=int,
                        help="run only N iterations (profiling)")
    parser.add_argument("--deterministic", action="store_true")
    parser.add_argument("--sync_bn", action="store_true",
                        help="use apex_tpu SyncBatchNorm across the mesh")
    parser.add_argument("--opt-level", type=str, default="O2")
    parser.add_argument("--keep-batchnorm-fp32", type=str, default=None)
    parser.add_argument("--loss-scale", type=str, default=None)
    parser.add_argument("--optimizer", choices=["sgd", "adam"], default="sgd",
                        help="FusedSGD (config #1) or FusedAdam (config #2)")
    parser.add_argument("--synthetic", action="store_true",
                        help="random data (throughput measurement; the "
                             "driver benches this mode)")
    parser.add_argument("--reuse-batches", default=0, type=int, metavar="N",
                        help="stage N synthetic batches on device once and "
                             "cycle them (what a prefetching input pipeline "
                             "reaches in steady state; use for step-time "
                             "measurement when host->device bandwidth is "
                             "not what you are measuring)")
    parser.add_argument("--steps-per-epoch", default=100, type=int,
                        help="synthetic epoch length")
    parser.add_argument("--image-size", default=224, type=int)
    parser.add_argument("--num-classes", default=1000, type=int)
    parser.add_argument("--half-dtype", choices=["bfloat16", "float16"],
                        default="bfloat16")
    parser.add_argument("--cpu", default=0, type=int, metavar="N",
                        help="force an N-virtual-device CPU mesh (the "
                             "single-host test harness; mirrors the "
                             "reference's 1-node multi-process launch)")
    return parser.parse_args()


def _force_cpu_mesh(n: int):
    """Must run before any jax backend initialisation (the device-count
    flag is read at start-up; the config update wins over an exported
    JAX_PLATFORMS)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}")
    jax.config.update("jax_platforms", "cpu")


class AverageMeter:
    """Reference ``main_amp.py:407-424``."""

    def __init__(self):
        self.reset()

    def reset(self):
        self.val = 0.0
        self.avg = 0.0
        self.sum = 0.0
        self.count = 0

    def update(self, val, n=1):
        self.val = val
        self.sum += val * n
        self.count += n
        self.avg = self.sum / self.count


def accuracy_topk(logits: jax.Array, target: jax.Array, topk=(1, 5)):
    """prec@k over the global batch (reference ``main_amp.py:427-440``)."""
    maxk = max(topk)
    _, pred = jax.lax.top_k(logits, maxk)
    correct = pred == target[:, None]
    return [100.0 * jnp.mean(jnp.any(correct[:, :k], axis=1).astype(jnp.float32))
            for k in topk]


def adjust_learning_rate(base_lr, epoch, step, len_epoch):
    """The reference schedule verbatim (``main_amp.py:470-486``)."""
    factor = epoch // 30
    if epoch >= 80:
        factor = factor + 1
    lr = base_lr * (0.1 ** factor)
    if epoch < 5:  # gradual warmup
        lr = lr * float(1 + step + epoch * len_epoch) / (5.0 * len_epoch)
    return lr


# ImageNet mean/std in 0..255 units — the reference's data_prefetcher
# normalises uint8 images on the GPU with these exact constants
# (``main_amp.py:204-209``); here the same normalisation runs on-device
# inside the jitted step, and the host only ships uint8.
_MEAN255 = np.array([0.485, 0.456, 0.406], np.float32) * 255.0
_STD255 = np.array([0.229, 0.224, 0.225], np.float32) * 255.0


def synthetic_batches(rng: np.random.Generator, n_steps, global_batch, size,
                      num_classes, dtype=None):
    del dtype  # images are uint8, like a real JPEG pipeline's fast_collate
    for _ in range(n_steps):
        x = rng.integers(0, 256, (global_batch, size, size, 3), dtype=np.uint8)
        y = rng.integers(0, num_classes, (global_batch,)).astype(np.int32)
        yield x, y


def _normalize(x, half_dtype, cast_input):
    """uint8 NHWC -> normalised float, on device (data_prefetcher analogue)."""
    x = (x.astype(jnp.float32) - _MEAN255) / _STD255
    return x.astype(half_dtype) if cast_input else x


def make_train_step(model, optimizer, scaler, mesh, half_dtype, cast_input):
    """One jitted SPMD train step: forward (mutable BN stats) -> scaled grads
    -> DDP psum -> fused optimizer with overflow skip -> scale update."""

    def loss_fn(params, batch_stats, x, y):
        logits, updates = model.apply(
            {"params": params, "batch_stats": batch_stats}, x, train=True,
            mutable=["batch_stats"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        loss = -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))
        return loss, (updates["batch_stats"], logits)

    grad_fn = amp.scaled_value_and_grad(loss_fn, scaler, has_aux=True)

    def step(params, batch_stats, opt_state, scaler_state, x, y, lr):
        x = _normalize(x, half_dtype, cast_input)
        (loss, (new_bstats, logits)), grads, sstate = grad_fn(
            scaler_state, params, batch_stats, x, y)
        grads = sync_gradients(grads, "data")
        loss = jax.lax.pmean(loss, "data")
        # overflow anywhere skips the step everywhere — the global found_inf
        # allreduce of the reference scaler (transformer/amp/grad_scaler.py:21)
        found_inf = jax.lax.psum(sstate.found_inf.astype(jnp.int32), "data") > 0
        sstate = sstate._replace(found_inf=found_inf)
        new_params, new_opt_state = optimizer.step(
            grads, opt_state, params, lr=lr, found_inf=found_inf)
        # BN running stats: averaged across the mesh (exact no-op under
        # SyncBN), and only updated on non-overflow steps, like the skipped
        # optimizer.step of the reference
        new_bstats = jax.tree_util.tree_map(
            lambda old, new: jnp.where(
                found_inf, old, jax.lax.pmean(new, "data")),
            batch_stats, new_bstats)
        new_sstate = scaler.update_scale(sstate)
        prec1, prec5 = accuracy_topk(logits, y)
        prec1 = jax.lax.pmean(prec1, "data")
        prec5 = jax.lax.pmean(prec5, "data")
        return (new_params, new_bstats, new_opt_state, new_sstate,
                loss, prec1, prec5)

    rep = P()
    sharded = P("data")
    inner = jax.shard_map(
        step, mesh=mesh,
        in_specs=(rep, rep, rep, rep, sharded, sharded, rep),
        out_specs=(rep, rep, rep, rep, rep, rep, rep),
        check_vma=True,
    )
    return jax.jit(inner, donate_argnums=(0, 1, 2, 3))


def make_eval_step(model, mesh, half_dtype, cast_input):
    def step(params, batch_stats, x, y):
        x = _normalize(x, half_dtype, cast_input)
        logits = model.apply(
            {"params": params, "batch_stats": batch_stats}, x, train=False)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        loss = -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))
        prec1, prec5 = accuracy_topk(logits, y)
        return (jax.lax.pmean(loss, "data"),
                jax.lax.pmean(prec1, "data"),
                jax.lax.pmean(prec5, "data"))

    inner = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(), P("data"), P("data")),
        out_specs=(P(), P(), P()),
        check_vma=True,
    )
    return jax.jit(inner)


def main(args=None):
    args = args or parse()
    if args.cpu:
        _force_cpu_mesh(args.cpu)
    if not args.synthetic:
        raise SystemExit(
            "a real JPEG input pipeline is not wired up in this port — run "
            "with --synthetic (the driver benches that mode); passing a data "
            "directory would otherwise silently train on noise"
        )
    if args.data:
        print(f"note: ignoring data dir {args.data!r} (synthetic mode)")
    print("opt_level =", args.opt_level)
    print("keep_batchnorm_fp32 =", args.keep_batchnorm_fp32)
    print("loss_scale =", args.loss_scale)

    devices = np.array(jax.devices())
    mesh = Mesh(devices, ("data",))
    world_size = devices.size
    if args.batch_size % world_size:
        raise SystemExit(
            f"global batch {args.batch_size} not divisible by {world_size} devices")
    print(f"devices: {world_size} x {devices.flat[0].device_kind}")

    half_dtype = jnp.bfloat16 if args.half_dtype == "bfloat16" else jnp.float16
    seed = 0 if args.deterministic else int(time.time())
    rng = np.random.default_rng(seed)

    model = resnet_lib.build_model(
        args.arch, num_classes=args.num_classes, sync_bn=args.sync_bn)
    variables = model.init(
        jax.random.PRNGKey(seed),
        jnp.zeros((2, args.image_size, args.image_size, 3), jnp.float32),
        train=False)
    params, batch_stats = variables["params"], variables.get("batch_stats", {})

    # lr scaled by global batch / 256, as the reference (`main_amp.py:167`)
    base_lr = args.lr * float(args.batch_size) / 256.0

    if args.optimizer == "sgd":
        optimizer = FusedSGD(lr=base_lr, momentum=args.momentum,
                             weight_decay=args.weight_decay)
    else:
        optimizer = FusedAdam(lr=base_lr, weight_decay=args.weight_decay)

    kbn = None
    if args.keep_batchnorm_fp32 is not None:
        kbn = args.keep_batchnorm_fp32.lower() == "true"
    loss_scale = None
    if args.loss_scale is not None:
        loss_scale = ("dynamic" if args.loss_scale == "dynamic"
                      else float(args.loss_scale))

    params, optimizer, amp_state = amp.initialize(
        params, optimizer, opt_level=args.opt_level,
        keep_batchnorm_fp32=kbn, loss_scale=loss_scale,
        half_dtype=half_dtype)
    scaler = amp_state.scaler(0)
    scaler_state = amp_state.scaler_state(0)
    opt_state = optimizer.init(params)

    # commit replicated state to the mesh up front so the first train_step
    # call already sees its steady-state shardings (avoids one recompile)
    rep_sharding = NamedSharding(mesh, P())
    params, batch_stats, opt_state, scaler_state = jax.device_put(
        (params, batch_stats, opt_state, scaler_state), rep_sharding)

    cast_input = amp_state.opt_properties.cast_model_type not in (None, jnp.float32)
    train_step = make_train_step(model, optimizer, scaler, mesh, half_dtype,
                                 cast_input)
    eval_step = make_eval_step(model, mesh, half_dtype, cast_input)

    start_epoch = args.start_epoch
    resumed_best_prec1 = 0.0
    if args.resume:
        if os.path.isfile(args.resume):
            with open(args.resume, "rb") as f:
                ck = pickle.load(f)
            params = jax.tree_util.tree_map(jnp.asarray, ck["params"])
            batch_stats = jax.tree_util.tree_map(jnp.asarray, ck["batch_stats"])
            opt_state = jax.tree_util.tree_map(jnp.asarray, ck["opt_state"])
            amp_state = amp_state.load_state_dict(ck["amp"])
            scaler_state = amp_state.scaler_state(0)
            start_epoch = ck["epoch"]
            resumed_best_prec1 = ck.get("best_prec1", 0.0)
            print(f"=> loaded checkpoint '{args.resume}' (epoch {start_epoch})")
        else:
            print(f"=> no checkpoint found at '{args.resume}'")

    len_epoch = args.steps_per_epoch
    if args.reuse_batches:
        data_sharding = NamedSharding(mesh, P("data"))
        staged = [
            (jax.device_put(jnp.asarray(x), data_sharding),
             jax.device_put(jnp.asarray(y), data_sharding))
            for x, y in synthetic_batches(
                rng, args.reuse_batches, args.batch_size, args.image_size,
                args.num_classes)
        ]

        def batches():
            for i in range(len_epoch):
                yield staged[i % len(staged)]
    else:
        batches = functools.partial(
            synthetic_batches, rng, len_epoch, args.batch_size,
            args.image_size, args.num_classes)

    if args.evaluate:
        validate(eval_step, params, batch_stats, batches(), args)
        return

    best_prec1 = resumed_best_prec1
    for epoch in range(start_epoch, args.epochs):
        batch_time = AverageMeter()
        losses = AverageMeter()
        top1 = AverageMeter()
        top5 = AverageMeter()

        end = time.time()
        last_print = -1
        for i, (x, y) in enumerate(batches()):
            if args.prof >= 0 and i > args.prof:
                print("Profiling ended at iteration", i)
                break
            lr = adjust_learning_rate(base_lr, epoch, i, len_epoch)
            (params, batch_stats, opt_state, scaler_state,
             loss, prec1, prec5) = train_step(
                params, batch_stats, opt_state, scaler_state,
                jnp.asarray(x), jnp.asarray(y), jnp.float32(lr))
            if i % args.print_freq == 0 or i == len_epoch - 1:
                jax.block_until_ready(loss)
                batch_time.update((time.time() - end) / (i - last_print))
                last_print = i
                losses.update(float(loss), args.batch_size)
                top1.update(float(prec1), args.batch_size)
                top5.update(float(prec5), args.batch_size)
                speed = args.batch_size / batch_time.val
                print(f"Epoch: [{epoch}][{i}/{len_epoch}]\t"
                      f"Time {batch_time.val:.3f} ({batch_time.avg:.3f})\t"
                      f"Speed {speed:.3f} ({args.batch_size / max(batch_time.avg, 1e-9):.3f})\t"
                      f"Loss {losses.val:.10f} ({losses.avg:.4f})\t"
                      f"Prec@1 {top1.val:.3f} ({top1.avg:.3f})\t"
                      f"Prec@5 {top5.val:.3f} ({top5.avg:.3f})")
                end = time.time()

        prec1 = validate(eval_step, params, batch_stats, batches(), args)
        is_best = prec1 > best_prec1
        best_prec1 = max(prec1, best_prec1)
        ck = {
            "epoch": epoch + 1,
            "arch": args.arch,
            "params": jax.tree_util.tree_map(np.asarray, params),
            "batch_stats": jax.tree_util.tree_map(np.asarray, batch_stats),
            "opt_state": jax.tree_util.tree_map(np.asarray, opt_state),
            "amp": amp_state.with_scaler_state(0, scaler_state).state_dict(),
            "best_prec1": best_prec1,
        }
        with open("checkpoint.pkl", "wb") as f:
            pickle.dump(ck, f)
        if is_best:
            with open("model_best.pkl", "wb") as f:
                pickle.dump(ck, f)

    return best_prec1


def validate(eval_step, params, batch_stats, batches, args):
    losses = AverageMeter()
    top1 = AverageMeter()
    top5 = AverageMeter()
    end = time.time()
    last_print = -1
    for i, (x, y) in enumerate(batches):
        loss, prec1, prec5 = eval_step(params, batch_stats,
                                       jnp.asarray(x), jnp.asarray(y))
        losses.update(float(loss), args.batch_size)
        top1.update(float(prec1), args.batch_size)
        top5.update(float(prec5), args.batch_size)
        if i % args.print_freq == 0:
            dt = (time.time() - end) / (i - last_print)
            last_print = i
            print(f"Test: [{i}]\t"
                  f"Speed {args.batch_size / max(dt, 1e-9):.3f}\t"
                  f"Loss {losses.val:.4f} ({losses.avg:.4f})\t"
                  f"Prec@1 {top1.val:.3f} ({top1.avg:.3f})\t"
                  f"Prec@5 {top5.val:.3f} ({top5.avg:.3f})")
            end = time.time()
    print(f" * Prec@1 {top1.avg:.3f} Prec@5 {top5.avg:.3f}")
    return top1.avg


if __name__ == "__main__":
    main()
