"""Fault-tolerant checkpointing (solver and LM training).

Design for 1000+ nodes:
  * atomic: stage to a uniquely-named ``step_XXXX.tmp-<uid>`` dir, then
    commit by renaming the old final dir aside before renaming the new
    one in — there is no instant at which ``step_XXXX`` is missing, and
    a crash anywhere leaves either the old or the new checkpoint intact;
  * verified: the manifest carries a CRC-32 per leaf; ``restore``
    re-checksums every array and raises ``CorruptCheckpointError`` on
    any damage (truncation, bit-flips, torn writes), which
    ``CheckpointManager.resume_or`` handles by walking back to the
    newest *intact* retained step;
  * manifest carries step, pytree structure and leaf checksums, so
    restore can re-shard onto a *different* device count (elastic
    restart — the Dykstra schedule's determinism makes dual re-sharding
    exact, DESIGN.md §6, and `launch/elastic.degrade_solver` is the
    consumer);
  * async: ``save_async`` snapshots **on device** (``snapshot_device``,
    a jitted tree copy — optionally donated, DESIGN.md §14) and both the
    device→host transfer and the write happen on a thread, so the solve
    never blocks on moving the full dual state; background failures are
    surfaced by ``wait_pending`` instead of being dropped, and retention
    GC never collects a step whose save is still in flight;
  * retention: keep the last ``keep`` checkpoints.

Failure injection (DESIGN.md §11): ``save``/``restore`` accept a
duck-typed ``faults`` injector (``serve.faults.FaultInjector``) polled
at the ``ckpt_save`` / ``ckpt_restore`` sites — truncate or corrupt the
staged arrays *before* the atomic commit, kill the process mid-save, or
report a step corrupt on read. This layer never imports serve.

Storage is .npz per checkpoint (offline container; on a real cluster
this layer is the integration point for a distributed store).
"""

from __future__ import annotations

import functools
import json
import os
import re
import sys
import shutil
import threading
import time
import uuid
import zipfile
import zlib

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "CheckpointError",
    "CheckpointManager",
    "CorruptCheckpointError",
    "latest_step",
    "restore",
    "save",
    "save_async",
    "snapshot_device",
    "wait_pending",
]


class CheckpointError(RuntimeError):
    """Structural checkpoint failure (wrong tree/shape for this run) —
    a caller bug, never auto-skipped."""


class CorruptCheckpointError(CheckpointError):
    """Unreadable or checksum-failed checkpoint — ``resume_or`` walks
    back past these to the newest intact step."""


# Only exact final dirs count as checkpoints; staging (.tmp-<uid>) and
# commit-aside (.old-<uid>) dirs never match.
_STEP_RE = re.compile(r"^step_(\d{8})$")

# Serializes commit/GC so retention can never unlink a directory that a
# concurrent commit is renaming.
_IO_LOCK = threading.RLock()

# Errors that mean "this checkpoint's bytes are bad", as opposed to a
# structure mismatch: np.load on a truncated/garbled .npz surfaces any
# of these depending on where the damage landed.
_READ_ERRORS = (OSError, ValueError, KeyError, EOFError, zipfile.BadZipFile, zlib.error)


def _flatten(tree):
    leaves, treedef = jax.tree.flatten(tree)
    return leaves, treedef


def _checksum(arr: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(arr).tobytes()) & 0xFFFFFFFF


def _apply_save_fault(spec, tmp: str) -> None:
    """Damage the *staged* checkpoint so the fault survives the atomic
    commit — exactly what a torn write or flaky disk produces."""
    npz = os.path.join(tmp, "arrays.npz")
    if spec.kind == "kill":
        sys.stdout.flush()
        os._exit(int(spec.payload.get("code", 17)))
    elif spec.kind == "ckpt_truncate":
        frac = float(spec.payload.get("fraction", 0.5))
        size = os.path.getsize(npz)
        with open(npz, "r+b") as fh:
            fh.truncate(max(1, int(size * frac)))
    elif spec.kind == "ckpt_corrupt":
        size = os.path.getsize(npz)
        with open(npz, "r+b") as fh:
            fh.seek(size // 2)
            fh.write(b"\xa5" * min(64, max(1, size - size // 2)))


def save(ckpt_dir: str, step: int, tree, extra: dict | None = None, faults=None) -> str:
    os.makedirs(ckpt_dir, exist_ok=True)
    leaves, treedef = _flatten(tree)
    uid = uuid.uuid4().hex[:8]
    name = f"step_{step:08d}"
    tmp = os.path.join(ckpt_dir, f"{name}.tmp-{uid}")
    final = os.path.join(ckpt_dir, name)
    os.makedirs(tmp)
    arrays = {f"leaf_{t}": np.asarray(leaf) for t, leaf in enumerate(leaves)}
    np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
    manifest = {
        "step": step,
        "num_leaves": len(leaves),
        "treedef": str(treedef),
        "checksums": {k: _checksum(a) for k, a in arrays.items()},
        "time": time.time(),
        "extra": extra or {},
    }
    with open(os.path.join(tmp, "manifest.json"), "w") as fh:
        json.dump(manifest, fh)
    if faults is not None:
        for spec in faults.poll("ckpt_save"):
            _apply_save_fault(spec, tmp)
    with _IO_LOCK:
        if os.path.exists(final):
            # Rename aside, swing the new dir in, then drop the old copy:
            # `final` exists (old or new) at every instant.
            aside = os.path.join(ckpt_dir, f"{name}.old-{uid}")
            os.rename(final, aside)
            os.rename(tmp, final)
            shutil.rmtree(aside, ignore_errors=True)
        else:
            os.rename(tmp, final)  # atomic commit
    return final


class _SaveThread(threading.Thread):
    """Background save whose failure is captured, not dropped."""

    def __init__(self, target, step):
        super().__init__(target=target, daemon=False)
        self.step = step
        self.error: BaseException | None = None

    def run(self):
        try:
            super().run()
        except BaseException as e:  # surfaced by wait_pending
            self.error = e


_PENDING: list[_SaveThread] = []
_PENDING_LOCK = threading.Lock()


def _copy_tree(tree):
    return jax.tree.map(jnp.copy, tree)


@jax.jit
def _snapshot_copy(tree):
    return _copy_tree(tree)


@functools.partial(jax.jit, donate_argnums=0)
def _snapshot_donate(tree):
    # Two aliasable outputs of one donated input: XLA reuses the donated
    # buffers for one of them, allocates the other — net one tree copy,
    # same as the non-donating path, but the caller's old reference is
    # consumed, which is what lets future pass programs donate the live
    # state without tripping on the snapshot alias.
    return tree, _copy_tree(tree)


def snapshot_device(tree, donate: bool = False):
    """On-device copy-on-save stage of an async checkpoint (DESIGN.md
    §14). Returns ``(live, snap)``: ``snap`` is a device-side copy whose
    host transfer can proceed on the writer thread while the solve keeps
    mutating ``live``; the caller-blocking cost is one asynchronously
    dispatched device copy, never the device→host transfer.

    ``donate=True`` donates the caller's tree into the snapshot program
    (backends that support donation reuse its buffers for ``live``); the
    caller MUST replace its state reference with the returned ``live``.
    On CPU — where XLA does not implement donation — the flag is ignored
    to keep the path warning-free.
    """
    if donate and jax.default_backend() != "cpu":
        return _snapshot_donate(tree)
    return tree, _snapshot_copy(tree)


def save_async(ckpt_dir: str, step: int, tree, extra: dict | None = None,
               faults=None, donate: bool = False):
    """Snapshot on device, then transfer + write on a background thread.

    The device→host transfer of the full dual state used to run on the
    caller before the thread started — at scale that serialized the solve
    against the snapshot for the whole transfer. Now the caller only
    dispatches a device-side copy (``snapshot_device``) and the writer
    thread pulls from the snapshot buffer.

    ``donate=False`` (default) returns the save thread, as before.
    ``donate=True`` additionally donates the live tree into the snapshot
    stage and returns ``(thread, live_tree)`` — the caller must rebind
    its state to ``live_tree`` (see ``CheckpointManager.maybe_save``).
    """
    live, snap = snapshot_device(tree, donate=donate)

    def _write():
        host_tree = jax.tree.map(lambda x: np.asarray(x), snap)
        save(ckpt_dir, step, host_tree, extra, faults=faults)

    th = _SaveThread(target=_write, step=step)
    th.start()
    with _PENDING_LOCK:
        _PENDING.append(th)
    return (th, live) if donate else th


def wait_pending():
    """Join all in-flight async saves; raise ``CheckpointError`` if any
    failed (first failure chained as the cause)."""
    with _PENDING_LOCK:
        pending, _PENDING[:] = list(_PENDING), []
    errors = []
    for th in pending:
        th.join()
        if th.error is not None:
            errors.append((th.step, th.error))
    if errors:
        step, first = errors[0]
        raise CheckpointError(
            f"{len(errors)} background checkpoint save(s) failed "
            f"(first: step {step}: {first!r})"
        ) from first


def _pending_steps() -> set[int]:
    with _PENDING_LOCK:
        return {th.step for th in _PENDING if th.error is None}


def _list_steps(ckpt_dir: str) -> list[int]:
    if not os.path.isdir(ckpt_dir):
        return []
    out = []
    for d in os.listdir(ckpt_dir):
        m = _STEP_RE.match(d)
        if m:
            out.append(int(m.group(1)))
    return sorted(out)


def latest_step(ckpt_dir: str) -> int | None:
    steps = _list_steps(ckpt_dir)
    return steps[-1] if steps else None


def clean_orphans(ckpt_dir: str) -> int:
    """Remove staging/aside dirs stranded by a crash or kill mid-save.
    Call at start of run, before any saves are in flight."""
    if not os.path.isdir(ckpt_dir):
        return 0
    n = 0
    with _IO_LOCK:
        for d in os.listdir(ckpt_dir):
            if re.match(r"^step_\d{8}\.(tmp|old)-", d):
                shutil.rmtree(os.path.join(ckpt_dir, d), ignore_errors=True)
                n += 1
    return n


def restore(ckpt_dir: str, tree_like, step: int | None = None, faults=None):
    """Restore into the structure of ``tree_like``. Returns
    (tree, manifest). Raises ``CorruptCheckpointError`` for damaged
    bytes or failed checksums, ``CheckpointError`` for a structure
    mismatch (which walking back cannot fix), ``FileNotFoundError``
    when the directory holds no checkpoints at all."""
    step = step if step is not None else latest_step(ckpt_dir)
    if step is None:
        raise FileNotFoundError(f"no checkpoints under {ckpt_dir}")
    path = os.path.join(ckpt_dir, f"step_{step:08d}")
    if faults is not None:
        for spec in faults.poll("ckpt_restore"):
            if spec.kind == "ckpt_corrupt":
                raise CorruptCheckpointError(
                    f"injected read fault: step {step} reported corrupt"
                )
    try:
        with open(os.path.join(path, "manifest.json")) as fh:
            manifest = json.load(fh)
        data = np.load(os.path.join(path, "arrays.npz"))
    except _READ_ERRORS as e:
        raise CorruptCheckpointError(f"step {step} unreadable: {e!r}") from e
    leaves_like, treedef = _flatten(tree_like)
    if manifest.get("num_leaves") != len(leaves_like):
        raise CheckpointError(
            f"structure mismatch at step {step}: checkpoint has "
            f"{manifest.get('num_leaves')} leaves, caller expects {len(leaves_like)}"
        )
    checksums = manifest.get("checksums", {})
    leaves = []
    for t, like in enumerate(leaves_like):
        key = f"leaf_{t}"
        try:
            arr = data[key]
        except _READ_ERRORS as e:
            raise CorruptCheckpointError(
                f"step {step} leaf {t} unreadable: {e!r}"
            ) from e
        if key in checksums and _checksum(arr) != checksums[key]:
            raise CorruptCheckpointError(
                f"step {step} leaf {t} failed CRC-32 verification"
            )
        if arr.shape != tuple(like.shape):
            raise CheckpointError(
                f"shape mismatch at step {step} leaf {t}: "
                f"{arr.shape} vs {tuple(like.shape)}"
            )
        leaves.append(arr.astype(like.dtype))
    return jax.tree.unflatten(treedef, leaves), manifest


class CheckpointManager:
    """Retention + auto-resume policy around save/restore.

    ``resume_or`` walks back over corrupt steps; retention GC is
    commit-lock-serialized and skips steps with in-flight async saves;
    stale staging dirs from a previous crashed run are swept at init.
    """

    def __init__(self, ckpt_dir: str, keep: int = 3, every: int = 100, faults=None):
        self.dir = ckpt_dir
        self.keep = keep
        self.every = every
        self.faults = faults
        clean_orphans(ckpt_dir)

    def maybe_save(self, step: int, tree, extra=None, asynchronous=True,
                   force=False, donate=False):
        """Save when ``step`` hits the cadence — or unconditionally with
        ``force=True`` (terminal state at convergence, which rarely lands
        on a multiple of ``every``).

        ``donate=True`` (async only) routes the donated copy-on-save
        snapshot and changes the return to ``(handle, live_tree)`` — the
        caller must rebind its state to ``live_tree``; on a skipped
        cadence that is ``(None, tree)`` unchanged. Idiom::

            _, state = mgr.maybe_save(step, state, donate=True)
        """
        if not force and step % self.every != 0:
            return (None, tree) if donate else None
        if donate and not asynchronous:
            raise ValueError("donate=True requires asynchronous=True: the "
                             "blocking save has no snapshot stage to donate "
                             "into")
        if asynchronous:
            out = save_async(self.dir, step, tree, extra, faults=self.faults,
                             donate=donate)
            if donate:
                out, tree = out
        else:
            out = save(self.dir, step, tree, extra, faults=self.faults)
        self._gc()
        return (out, tree) if donate else out

    def _gc(self):
        with _IO_LOCK:
            steps = _list_steps(self.dir)
            busy = _pending_steps()
            for s in steps[: -self.keep]:
                if s in busy:
                    continue
                shutil.rmtree(
                    os.path.join(self.dir, f"step_{s:08d}"), ignore_errors=True
                )

    def resume_or(self, init_tree, check=None):
        """Restore the newest *intact* retained step, walking back over
        corrupt ones; fall through to ``(init_tree, 0)`` when nothing
        usable survives. Structure mismatches still raise, and so does
        ``check``, called with the restored step's manifest before it is
        accepted (a step it refuses is not corrupt, so walking back to an
        older one cannot help)."""
        for s in reversed(_list_steps(self.dir)):
            try:
                tree, manifest = restore(self.dir, init_tree, s, faults=self.faults)
            except CorruptCheckpointError:
                continue
            if check is not None:
                check(manifest)
            return tree, manifest["step"]
        return init_tree, 0
