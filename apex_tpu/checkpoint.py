"""Checkpoint save/load — tensorstore-backed, sharding-aware.

The reference scatters checkpointing across per-component ``state_dict``s
(amp scaler ``apex/amp/frontend.py:365-404``, ``FP16_Optimizer.state_dict``
``fp16_utils/fp16_optimizer.py:212-273``, DistributedFusedAdam's v1
gather-on-root / v2 per-rank-shard formats
``contrib/optimizers/distributed_fused_adam.py:2956-3555``) and leaves the
file IO to ``torch.save`` or cuFile (``csrc/gpu_direct_storage/gds.cpp``).

TPU-native: orbax/tensorstore owns the device<->storage path (the
GPUDirect-Storage analogue — XLA device buffers stream to storage without a
host round-trip where the platform supports it), and **sharded jax.Arrays
checkpoint natively**: each host writes its own shards (the v2 format's
property), and restore takes an abstract target carrying the desired
shardings so a checkpoint can be loaded onto a different mesh layout
(the v1 gather/rescatter property) — both formats collapse into one
mechanism here.

API::

    save_checkpoint(path, {"params": params, "opt_state": state, "step": 3})
    restored = load_checkpoint(path)                      # host numpy
    restored = load_checkpoint(path, target=abstract_or_concrete_tree)
    # target leaves may be jax.ShapeDtypeStruct(shape, dtype, sharding=...)

``amp.AmpState``/scaler states and the fused optimizers' NamedTuple states
are plain pytrees — they round-trip as-is.

Durability (the ``apex_tpu.resilience`` contract): ``save_checkpoint``
stages the write into a same-directory ``<path>.tmp-<pid>`` and renames
into place only after the checkpointer has fully committed, so a crash or
preemption mid-write can never leave a half-written tree AT the final
path — whatever was at ``path`` before the save stays loadable.
``load_checkpoint`` converts storage-level failures (truncated
tensorstore files, missing arrays, a checkpoint that never committed)
into the typed :class:`CheckpointCorruptError`, which
``resilience.CheckpointManager`` catches to fall back to the newest good
step instead of dying on an orbax traceback.
"""
from __future__ import annotations

import os
import shutil
import threading
import time
from typing import Any, Optional

import jax
import numpy as np

Pytree = Any


class CheckpointCorruptError(RuntimeError):
    """A checkpoint exists at ``path`` but cannot be restored.

    Raised by :func:`load_checkpoint` for storage-level failures —
    truncated or missing tensorstore files, a partially-deleted tree, a
    write that never committed. The original backend exception rides as
    ``__cause__``. ``resilience.CheckpointManager.restore`` catches this
    (and only this) to fall back to an older step.
    """

    def __init__(self, path: str, cause: Optional[BaseException] = None):
        self.path = path
        detail = f": {type(cause).__name__}: {cause}" if cause else ""
        super().__init__(f"corrupt or unreadable checkpoint at {path}{detail}")


def _checkpointer():
    import orbax.checkpoint as ocp

    return ocp.StandardCheckpointer()


# orbax keys the signals between its save threads by a PROCESS-global
# operation id: two saves in flight in one process (async manager
# threads, in-process fake hosts) read each other's id, and the loser
# waits out orbax's 300 s signal timeout. One save at a time per process.
_SAVE_LOCK = threading.Lock()


def _save_and_wait(ckptr, path: str, state: Pytree, *, force: bool) -> None:
    with _SAVE_LOCK:
        ckptr.save(path, state, force=force)
        ckptr.wait_until_finished()


def _sweep_failed_write(tmp: str) -> None:
    """Remove a failed write's staging trees: ours and orbax's own
    ``<tmp>.orbax-checkpoint-tmp`` sibling. orbax creates that sibling on
    a background thread nothing joins, so after a save that failed
    validation it can land a moment AFTER the error surfaced — look
    again, briefly, until it has shown up."""
    import glob

    deadline = time.monotonic() + 1.0
    while True:
        staged = glob.glob(glob.escape(tmp) + ".orbax-checkpoint-tmp*")
        for leftover in [tmp] + staged:
            shutil.rmtree(leftover, ignore_errors=True)
        if staged or time.monotonic() > deadline:
            return
        time.sleep(0.05)


def fsync_file(path: str) -> None:
    """Flush one file's data+metadata to stable storage.

    ``os.rename`` orders nothing by itself: a machine crash (power loss,
    not just a process kill) straddling a tmp+rename commit can leave
    the rename durable while the renamed tree's *contents* are still in
    the page cache — a committed-looking checkpoint full of zero-length
    files. Callers fsync the payload files, then the directory entries
    (:func:`fsync_dir`), then rename, then fsync the parent."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def fsync_dir(path: str) -> None:
    """Flush a directory's entries (creations/renames inside it) to
    stable storage — the other half of a durable rename commit. On
    platforms where directories cannot be opened/fsynced (Windows), the
    flush is skipped: the atomicity story there is process-crash-only,
    which matches the rest of this module."""
    flags = os.O_RDONLY | getattr(os, "O_DIRECTORY", 0)
    try:
        fd = os.open(path, flags)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass  # some filesystems refuse directory fsync; best effort
    finally:
        os.close(fd)


def fsync_tree(path: str) -> None:
    """Flush a whole staged checkpoint tree — every regular file
    (:func:`fsync_file`) and every directory (:func:`fsync_dir`),
    bottom-up — before the rename that commits it. This is the payload
    half of durability: the tensorstore array files orbax wrote give no
    page-cache guarantee of their own, and a machine crash after a
    durable rename but before their writeback would leave a
    committed-looking step of empty files."""
    for dirpath, _dirnames, filenames in os.walk(path, topdown=False):
        for fn in filenames:
            try:
                fsync_file(os.path.join(dirpath, fn))
            except OSError:
                pass  # vanished/unreadable entries are best effort
        fsync_dir(dirpath)


def stale_writer(pid: int) -> bool:
    """True when a ``*.tmp-<pid>`` staging tree cannot still be being
    written: the pid is our own (a prior call in this process left it
    behind) or no longer exists. Pids we cannot probe (EPERM: exists,
    different user) are treated as live. Shared by this module's sweep
    and ``resilience.CheckpointManager._sweep_stale_tmp`` — only valid
    for LOCAL pids, which is why sweeping is skipped in multi-process
    runs."""
    if pid == os.getpid():
        return True
    try:
        os.kill(pid, 0)
        return False
    except ProcessLookupError:
        return True
    except OSError:
        return False


def save_checkpoint(path: str, state: Pytree, *, overwrite: bool = True,
                    staged: bool = True) -> None:
    """Write a pytree of (possibly sharded) arrays/scalars to ``path``.

    Sharded ``jax.Array`` leaves are written shard-by-shard (every process
    writes only its addressable shards — the reference's v2 sharded format,
    ``distributed_fused_adam.py:3339+``); replicated and host values are
    written once.

    The write is atomic at the directory level **in single-process
    runs**: it lands in ``<path>.tmp-<pid>`` and is renamed over
    ``path`` only once complete (same filesystem, so the rename itself
    is atomic). On any failure the partial tmp tree is removed and
    whatever previously lived at ``path`` is untouched. Multi-process
    runs hand orbax the final path directly — every process must agree
    on ONE directory for its shards and the commit is coordinated by
    orbax's own finalization; a per-process tmp+rename would scatter
    shards across private directories (and local pid liveness means
    nothing across hosts, so no tmp sweeping happens there either).

    ``staged=False`` skips the tmp+rename+stale-sweep entirely: for
    callers whose ``path`` already sits inside their OWN uncommitted
    staging directory (``resilience.CheckpointManager._write`` renames a
    whole ``step_X.tmp-<pid>`` tree at commit), an inner staging layer
    would be pure overhead and a second copy of the sweep/rename
    invariants to keep consistent.
    """
    import glob
    import re

    path = os.path.abspath(path)
    if not overwrite and os.path.exists(path):
        # fail BEFORE staging the (potentially many-GB) write
        raise FileExistsError(
            f"checkpoint exists at {path} and overwrite=False")
    ckptr = _checkpointer()
    if not staged or jax.process_count() > 1:
        _save_and_wait(ckptr, path, state, force=overwrite)
        return
    tmp = f"{path}.tmp-{os.getpid()}"
    # sweep stale partials — ours, and any whose writer pid is dead (a
    # crashed previous process leaves its full-size tmp behind with a
    # DIFFERENT pid in the name; without this, crash/restart cycles
    # leak one state-size tree each)
    for stale in glob.glob(glob.escape(path) + ".tmp-*"):
        # matches both our staging dirs (<path>.tmp-<pid>) and orbax's
        # own staging siblings (<path>.tmp-<pid>.orbax-checkpoint-tmp)
        m = re.search(r"\.tmp-(\d+)", os.path.basename(stale))
        if m is not None and stale_writer(int(m.group(1))):
            shutil.rmtree(stale, ignore_errors=True)
    try:
        _save_and_wait(ckptr, tmp, state, force=True)
    except BaseException:
        _sweep_failed_write(tmp)
        raise
    if os.path.exists(path):
        if not overwrite:  # appeared during the write
            shutil.rmtree(tmp, ignore_errors=True)
            raise FileExistsError(
                f"checkpoint exists at {path} and overwrite=False")
        # the only non-atomic window: the old tree is dropped before the
        # new one is renamed in. resilience.CheckpointManager never
        # overwrites (one directory per step), so it has no such window.
        shutil.rmtree(path)
    os.rename(tmp, path)


def load_checkpoint(path: str, target: Optional[Pytree] = None) -> Pytree:
    """Read a checkpoint.

    Without ``target``: returns host-side arrays in the saved structure.
    With ``target``: a matching pytree of abstract leaves
    (``jax.ShapeDtypeStruct`` with an optional ``sharding``) or concrete
    arrays whose shardings describe where each leaf should land — restore
    places shards directly on the right devices, including onto a
    *different* mesh than the one that saved (the v1 format's
    gather/rescatter capability without the gather).

    Raises :class:`FileNotFoundError` when nothing exists at ``path`` and
    :class:`CheckpointCorruptError` when something does but the restore
    fails at the storage layer (truncated files, missing arrays, an
    uncommitted write).
    """
    path = os.path.abspath(path)
    if not os.path.exists(path):
        raise FileNotFoundError(f"no checkpoint at {path}")
    ckptr = _checkpointer()
    if target is None:
        try:
            return ckptr.restore(path)
        except Exception as e:
            raise CheckpointCorruptError(path, e) from e

    def to_abstract(leaf):
        if isinstance(leaf, jax.ShapeDtypeStruct):
            return leaf
        if isinstance(leaf, jax.Array):
            return jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=leaf.sharding
            )
        if isinstance(leaf, np.ndarray):
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
        return leaf  # scalars and strings restore as saved

    abstract = jax.tree_util.tree_map(to_abstract, target)
    try:
        return ckptr.restore(path, abstract)
    except Exception as e:
        # truncated tensorstore files surface as ValueError/OSError deep
        # inside the backend — indistinguishable by type from a bad
        # target template, so everything is wrapped; the original rides
        # as __cause__ for triage
        raise CheckpointCorruptError(path, e) from e
