"""Distributed (sharded, async, reshardable, atomically committed)
checkpointing.

reference parity: fleet.save_persistables / fleet_base.py:779 (per-variable
persistable save through the executor), operators/save_op.cc /
load_op.cc (one file per variable), plus the reference's separate
save_inference_model flow. SURVEY §7.9 asks for *surpassing* this with a
sharded async checkpoint + reshard-on-resume — this module is that
implementation.

TPU-native design: checkpoints are orbax/tensorstore OCDBT trees.
- **Sharded**: each host writes only the array shards it owns; nothing is
  ever gathered to one host (the reference funnels every persistable
  through the trainer-0 executor).
- **Async**: `save(..., asynchronous=True)` returns after enqueueing —
  device arrays are snapshotted, serialization overlaps the next training
  steps (reference saving blocks the trainer).
- **Reshard-on-load**: restore takes the *target* layout (mesh +
  PartitionSpecs), not the saved one; a checkpoint written on a
  dp4×mp2 mesh restores onto dp2×mp4 (or a single chip) with each
  device reading exactly its slice.
- **Atomic commit** (CheckFreq-style, docs/FAULT_TOLERANCE.md): every
  save serializes into ``<path>.tmp``, then a *commit* writes an
  fsync'd manifest (per-leaf tree paths/dtypes/shapes, per-file sizes +
  CRC32s, step, flags fingerprint) and atomically renames the staging
  dir onto ``<path>``. A process killed mid-save leaves only a ``.tmp``
  dir — :func:`latest_step` and :func:`load` skip uncommitted or
  verification-failing directories (``FLAGS_checkpoint_verify``:
  off|manifest|full) and fall back to the newest *valid* checkpoint,
  recording a ``checkpoint_fallback`` flight-recorder event.
"""

from __future__ import annotations

import json
import logging
import os
import shutil
import time
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["save", "load", "wait", "save_train_step", "load_train_step",
           "latest_step", "checkpoint_steps", "verify_checkpoint",
           "Checkpointer", "CheckpointError", "MANIFEST_NAME",
           "STAGING_SUFFIX", "CheckpointManager", "PreemptionSignal"]

logger = logging.getLogger("paddle_tpu.checkpoint")

MANIFEST_NAME = "paddle_tpu_manifest.json"
STAGING_SUFFIX = ".tmp"
REPLACED_SUFFIX = ".old"    # being-replaced checkpoint parked here for
                            # the two renames of a same-path re-commit


class CheckpointError(RuntimeError):
    """A checkpoint save failed or a restore target failed verification."""


def _ocp():
    import orbax.checkpoint as ocp
    return ocp


# ---------------------------------------------------------------------------
# Commit protocol
# ---------------------------------------------------------------------------

def _leaf_manifest(state) -> Dict[str, dict]:
    """Host-side metadata of every array leaf (no device sync): tree
    path -> {shape, dtype}. Scalars/strings are recorded by type."""
    leaves = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        key = jax.tree_util.keystr(path)
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            leaves[key] = {"shape": list(np.shape(leaf)),
                           "dtype": str(leaf.dtype)}
        else:
            leaves[key] = {"type": type(leaf).__name__}
    return leaves


def _flags_fingerprint() -> Dict[str, Any]:
    """Full flags snapshot at save time: a resume under different flags
    (layouts, chunking) is a legitimate thing to want to know post-hoc."""
    try:
        from ...core import flags as F
        out = {}
        for name in sorted(F._REGISTRY):
            try:
                v = F.get_flag(name)
            except Exception:
                continue
            out[name] = v if isinstance(v, (bool, int, float, str,
                                            type(None))) else repr(v)
        return out
    except Exception:
        return {}


def _crc32_file(path: str) -> int:
    crc = 0
    with open(path, "rb") as f:
        while True:
            chunk = f.read(1 << 20)
            if not chunk:
                return crc
            crc = zlib.crc32(chunk, crc)


def _file_entries(root: str, checksum: bool = True) -> Dict[str, dict]:
    files = {}
    for dirpath, _dirs, names in os.walk(root):
        for name in names:
            if dirpath == root and name == MANIFEST_NAME:
                continue
            full = os.path.join(dirpath, name)
            rel = os.path.relpath(full, root)
            entry = {"size": os.path.getsize(full)}
            if checksum:
                entry["crc32"] = _crc32_file(full)
            files[rel] = entry
    return files


def _fsync_file(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _fsync_dir(path: str) -> None:
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass          # some filesystems refuse dir fsync; rename is
    finally:          # still ordered after the manifest's file fsync
        os.close(fd)


def _record_event(event: str, **fields) -> None:
    """Flight-recorder event, gated exactly like TrainStep records."""
    try:
        from ...monitor.flight_recorder import safe_record_event
    except Exception:
        return
    safe_record_event(event, **fields)


def _commit(tmp: str, final: str, leaves: Dict[str, dict],
            extra_files: Optional[Dict[str, str]],
            step: Optional[int]) -> None:
    """Turn a finished staging dir into a committed checkpoint: write
    extra files + manifest (fsync'd), then atomically rename. A crash at
    ANY point before the rename leaves only the ``.tmp`` dir, which
    every reader skips. The commit is a ``checkpoint.commit`` span: in
    the span ring, and under the step trace when one is active
    (FLAGS_trace)."""
    from ...monitor import trace as _trace_mod
    with _trace_mod.span("checkpoint.commit", step=step, path=final):
        _commit_impl(tmp, final, leaves, extra_files, step)


def _commit_impl(tmp: str, final: str, leaves: Dict[str, dict],
                 extra_files: Optional[Dict[str, str]],
                 step: Optional[int]) -> None:
    from ...testing import chaos

    for name, data in (extra_files or {}).items():
        p = os.path.join(tmp, name)
        with open(p, "w") as f:
            f.write(data)
        _fsync_file(p)
    # CRC32s require re-reading the whole staged tree on the training
    # thread — only pay that when the configured verify level will
    # actually use them. A manifest without CRCs still verifies at
    # 'manifest' (sizes) and 'full' skips absent checksums.
    try:
        from ...core.flags import get_flag
        checksum = get_flag("checkpoint_verify") == "full"
    except Exception:
        checksum = False
    files = _file_entries(tmp, checksum=checksum)
    manifest = {"format": 1,
                "step": step,
                "created": time.time(),
                "flags": _flags_fingerprint(),
                "leaves": leaves,
                "files": files}
    mpath = os.path.join(tmp, MANIFEST_NAME)
    with open(mpath, "w") as f:
        json.dump(manifest, f, indent=1)
        f.flush()
        os.fsync(f.fileno())
    if chaos.active():
        # torn write racing the commit: a data file loses its tail AFTER
        # its checksum was recorded — verification must catch this
        if chaos.probe("ckpt.write.torn") and files:
            victim = max(files, key=lambda r: files[r]["size"])
            vp = os.path.join(tmp, victim)
            with open(vp, "r+b") as f:
                f.truncate(max(0, files[victim]["size"] // 2))
        if chaos.probe("ckpt.manifest.corrupt"):
            with open(mpath, "wb") as f:
                f.write(b"\x00garbage\x00" * 4)
    _fsync_dir(tmp)
    # Replacing an existing committed checkpoint must not open a window
    # where a crash leaves NOTHING valid: rename the old one aside
    # (readers skip the .old name), swap the new one in, then delete.
    # A crash between the two renames hides the old step (its content
    # survives on disk under .old) — a two-syscall window, versus the
    # whole rmtree of a multi-GB tree if we deleted first.
    old = None
    if os.path.exists(final):
        old = final + REPLACED_SUFFIX
        if os.path.isdir(old):
            shutil.rmtree(old)
        elif os.path.exists(old):
            os.remove(old)
        os.rename(final, old)
    os.rename(tmp, final)
    _fsync_dir(os.path.dirname(final) or ".")
    if old is not None:
        shutil.rmtree(old, ignore_errors=True)
    total = sum(e["size"] for e in files.values())
    _record_event("checkpoint_commit", path=final, step=step,
                  files=len(files), bytes=total)
    logger.info("checkpoint committed: %s (%d files, %d bytes)",
                final, len(files), total)


def verify_checkpoint(path: str, level: Optional[str] = None) \
        -> Optional[str]:
    """Validate a committed checkpoint directory. Returns None when
    valid, else a human-readable reason. ``level`` defaults to
    ``FLAGS_checkpoint_verify`` (off|manifest|full)."""
    if level is None:
        from ...core.flags import get_flag
        level = get_flag("checkpoint_verify")
    if not os.path.isdir(path):
        return "missing (not a directory)"
    if level == "off":
        return None
    mpath = os.path.join(path, MANIFEST_NAME)
    if not os.path.exists(mpath):
        return "uncommitted (no manifest)"
    try:
        with open(mpath) as f:
            manifest = json.load(f)
        files = manifest["files"]
    except (ValueError, KeyError, OSError) as e:
        return f"manifest unreadable ({type(e).__name__}: {e})"
    for rel, entry in files.items():
        full = os.path.join(path, rel)
        if not os.path.exists(full):
            return f"file missing: {rel}"
        size = os.path.getsize(full)
        if size != entry.get("size"):
            return (f"torn file: {rel} is {size} bytes, manifest says "
                    f"{entry.get('size')}")
        if level == "full" and "crc32" in entry:
            if _crc32_file(full) != entry["crc32"]:
                return f"checksum mismatch: {rel}"
    return None


def read_manifest(path: str) -> Optional[dict]:
    """The committed manifest of a checkpoint dir, or None."""
    try:
        with open(os.path.join(path, MANIFEST_NAME)) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


# ---------------------------------------------------------------------------
# Checkpointer
# ---------------------------------------------------------------------------

class Checkpointer:
    """Process-wide async checkpointer (one background serialization
    thread; concurrent saves to different paths queue behind it).

    Commit discipline: async saves serialize into ``<path>.tmp`` and are
    committed (manifest + rename) by :meth:`wait` — a checkpoint is
    durable-and-visible only after ``wait()`` returns. ``wait`` and the
    next ``save`` RE-RAISE background-save failures as
    :class:`CheckpointError`; a failed save can never silently pass for
    a checkpoint."""

    _instance: Optional["Checkpointer"] = None

    def __init__(self):
        ocp = _ocp()
        self._async = ocp.AsyncCheckpointer(ocp.PyTreeCheckpointHandler())
        self._sync = ocp.PyTreeCheckpointer()
        # the one async save awaiting commit: (tmp, final, leaves,
        # extra_files, step). At most ONE can be outstanding — save()
        # finalizes any pending entry before enqueueing (the async
        # checkpointer serializes behind one thread anyway).
        self._pending: Optional[Tuple[str, str, dict, Optional[dict],
                                      Optional[int]]] = None

    @classmethod
    def instance(cls) -> "Checkpointer":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def save(self, path: str, state, asynchronous: bool = True,
             extra_files: Optional[Dict[str, str]] = None,
             step: Optional[int] = None):
        # a still-pending (or failed) earlier save is finalized first:
        # its staging dir may be THIS path's, and its failure must
        # surface here rather than evaporate
        if self._pending:
            self.wait()
        path = os.path.abspath(path)
        tmp = path + STAGING_SUFFIX
        if os.path.isdir(tmp):
            shutil.rmtree(tmp)          # orphan from a killed process
        leaves = _leaf_manifest(state)
        if asynchronous:
            self._async.save(tmp, state, force=True)
            self._pending = (tmp, path, leaves, extra_files, step)
        else:
            self._sync.save(tmp, state, force=True)
            _commit(tmp, path, leaves, extra_files, step)

    def pending_ready(self) -> bool:
        """True when the pending async save has FINISHED serializing, so
        :meth:`wait` would commit without blocking. Best-effort probe of
        the orbax background thread (private attr, pinned version) —
        False when there is nothing pending or the answer is unknown.
        Lets the training loop commit at the first step boundary after
        serialization completes instead of at the next interval
        (CheckFreq: worst-case loss = one interval, not two)."""
        if self._pending is None:
            return False
        try:
            thread = getattr(self._async, "_thread", None)
            return thread is None or not thread.is_alive()
        except Exception:
            return False

    def wait(self):
        """Block until the pending async save is durable AND committed.
        Re-raises any background serialization/commit failure — the run
        must not continue believing it has a checkpoint it doesn't."""
        pending, self._pending = self._pending, None
        try:
            self._async.wait_until_finished()
            if hasattr(self._async, "check_for_errors"):
                self._async.check_for_errors()
        except Exception as e:
            if pending is not None:
                shutil.rmtree(pending[0], ignore_errors=True)
            raise CheckpointError(
                f"async checkpoint save failed: {e!r} (staging dir "
                "removed; the previous committed checkpoint is intact)"
            ) from e
        if pending is None:
            return
        tmp, final, leaves, extra_files, step = pending
        try:
            _commit(tmp, final, leaves, extra_files, step)
        except Exception as e:
            shutil.rmtree(tmp, ignore_errors=True)
            raise CheckpointError(
                f"checkpoint commit failed: {final}: {e!r}") from e

    def restore(self, path: str, target=None):
        ocp = _ocp()
        path = os.path.abspath(path)
        reason = verify_checkpoint(path)
        if reason is not None:
            raise CheckpointError(
                f"refusing to restore {path}: {reason}. Use "
                "latest_step()/CheckpointManager.resume() for automatic "
                "fallback to the newest valid checkpoint, or "
                "FLAGS_checkpoint_verify=off for legacy dirs.")
        if target is None:
            return self._sync.restore(path)
        restore_args = ocp.checkpoint_utils.construct_restore_args(target)
        return self._sync.restore(path, restore_args=restore_args)


def save(state: Dict[str, Any], path: str, asynchronous: bool = True,
         extra_files: Optional[Dict[str, str]] = None,
         step: Optional[int] = None):
    """Sharded save of a pytree of (possibly distributed) arrays.

    With ``asynchronous=True`` (default) the call returns once device
    arrays are snapshotted; call :func:`wait` to block until the files
    are durable AND the checkpoint is committed (manifest + atomic
    rename — done automatically before the next save of the same
    checkpointer). ``extra_files`` (name -> text) are committed inside
    the checkpoint dir and covered by the manifest."""
    Checkpointer.instance().save(path, state, asynchronous,
                                 extra_files=extra_files, step=step)


def wait():
    """Block until all pending async saves are durable on disk and
    committed; re-raises background-save failures."""
    Checkpointer.instance().wait()


def load(path: str, target=None):
    """Restore a checkpoint (verification per FLAGS_checkpoint_verify
    runs first; an uncommitted/torn dir raises CheckpointError).

    ``target`` (optional) is a pytree of arrays or ShapeDtypeStructs
    declaring the desired dtypes AND shardings — arrays restore directly
    into that layout (reshard-on-load). Without it, arrays restore with
    their saved shardings (requires the same topology)."""
    return Checkpointer.instance().restore(path, target)


def checkpoint_steps(root: str) -> List[int]:
    """Committed ``step_<N>`` directory numbers under ``root``
    (ascending; staging ``.tmp`` dirs excluded, validity NOT checked)."""
    if not os.path.isdir(root):
        return []
    steps = []
    for name in os.listdir(root):
        if not name.startswith("step_") or name.endswith(STAGING_SUFFIX):
            continue
        try:
            steps.append(int(name.split("_", 1)[1]))
        except ValueError:
            pass
    return sorted(steps)


def latest_step(root: str) -> Optional[int]:
    """Highest *valid* ``step_<N>`` checkpoint under ``root``.

    Uncommitted (``.tmp`` / manifest-less) and verification-failing
    directories are skipped with a ``checkpoint_fallback`` flight event
    and a warning — the torn last save of a killed run must never be the
    resume point."""
    skipped = []
    for n in reversed(checkpoint_steps(root)):
        path = os.path.join(root, f"step_{n}")
        reason = verify_checkpoint(path)
        if reason is None:
            for bad_n, bad_reason in skipped:
                _record_event("checkpoint_fallback", step=bad_n,
                              reason=bad_reason, fallback_to=n)
            return n
        skipped.append((n, reason))
        logger.warning("skipping invalid checkpoint %s: %s", path, reason)
    for bad_n, bad_reason in skipped:
        _record_event("checkpoint_fallback", step=bad_n,
                      reason=bad_reason, fallback_to=None)
    return None


# -- TrainStep integration ---------------------------------------------------


def _listify(tree):
    """Tuples -> lists recursively: orbax round-trips tuple nodes as
    lists, so both the saved state and the restore target use lists and
    the caller rebuilds its native structure afterwards."""
    if isinstance(tree, (tuple, list)):
        return [_listify(x) for x in tree]
    if isinstance(tree, dict):
        return {k: _listify(v) for k, v in tree.items()}
    return tree


def _train_step_target(step) -> Dict[str, Any]:
    """Target pytree for restoring INTO a TrainStep's current layout: every
    array leaf becomes a ShapeDtypeStruct carrying the step's mesh +
    PartitionSpec — the reshard-on-load declaration."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = step.mesh

    def sds(a, spec):
        if not hasattr(a, "shape") or getattr(a, "ndim", 0) is None:
            return a
        if mesh is None:
            return jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)
        return jax.ShapeDtypeStruct(
            np.shape(a), np.asarray(a).dtype if not hasattr(a, "dtype")
            else a.dtype, sharding=NamedSharding(mesh, spec or P()))

    specs = step._param_specs() if mesh is not None else {}
    frozen_specs = {}
    if mesh is not None:
        frozen_specs = {k: getattr(p, "spec", None) or P()
                        for k, p in step.layer.named_parameters()
                        if k not in step.params}

    target = {
        "params": {k: sds(v, specs.get(k))
                   for k, v in step.params.items()},
        "frozen": {k: sds(v, frozen_specs.get(k))
                   for k, v in step.frozen.items()},
        "buffers": {k: sds(v, None) for k, v in step.buffers.items()},
        "opt_state": {
            k: jax.tree_util.tree_map(
                lambda a, k=k: sds(
                    a, step._slot_spec(k, np.shape(a))
                    if mesh is not None and getattr(a, "ndim", 0) > 0
                    else None)
                if hasattr(a, "shape") else a, v)
            for k, v in step.opt_state.items()},
        "step_count": 0,
        # orbax round-trips tuples as lists; declare a list on both sides
        "rng_state": [0, 0],
        "lr": 0.0,
    }
    if mesh is not None:
        step._specs = specs
    return _listify(target)


def save_train_step(step, path: str, asynchronous: bool = True,
                    extra_files: Optional[Dict[str, str]] = None):
    """Sharded (async) save of a TrainStep's full training state — params,
    frozen params, buffers, optimizer slots, step count, RNG, LR. The
    distributed analogue of TrainStep.save (whole-state pickle)."""
    from ...core.random import default_generator

    state = {
        "params": dict(step.params),
        "frozen": dict(step.frozen),
        "buffers": dict(step.buffers),
        "opt_state": step.opt_state,
        "step_count": step.step_count,
        "rng_state": [int(x) for x in default_generator().get_state()],
        "lr": float(step.optimizer.get_lr()),
    }
    save(_listify(state), path, asynchronous=asynchronous,
         extra_files=extra_files, step=int(step.step_count))


def load_train_step(step, path: str):
    """Restore a sharded checkpoint INTO a TrainStep, resharding every
    array to the step's *current* mesh/PartitionSpec layout (which may be
    a different factorization — or single-chip — than at save time)."""
    from ...core.random import default_generator

    target = _train_step_target(step)
    state = load(path, target=target)

    # Re-materialize every restored leaf into a fresh framework-owned
    # device buffer (sharding-preserving). The restore hands back arrays
    # whose storage the checkpoint layer owns; feeding those straight into
    # the TrainStep's donated executable makes XLA free/alias foreign
    # buffers — a hard crash (SIGSEGV on XLA:CPU) on the first step after
    # a reshard-on-load. One copy per leaf at restore time is noise next
    # to checkpoint I/O.
    def _own(a):
        return jnp.copy(a) if isinstance(a, jax.Array) else a

    step.params = jax.tree_util.tree_map(_own, dict(state["params"]))
    step.frozen = jax.tree_util.tree_map(_own, dict(state["frozen"]))
    step.buffers = jax.tree_util.tree_map(_own, dict(state["buffers"]))
    # rebuild the optimizer's native container structure (listified for
    # serialization) from the restored leaves
    step.opt_state = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(step.opt_state),
        jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(_own, state["opt_state"])))
    step.step_count = int(state["step_count"])
    # restore starts a fresh gradient-accumulation window
    step._acc_grads = None
    step._micro_count = 0
    rng = state.get("rng_state")
    if rng is not None:
        default_generator().set_state(tuple(int(x) for x in rng))
    lr = state.get("lr")
    if lr is not None and hasattr(step.optimizer, "set_lr"):
        try:
            step.optimizer.set_lr(float(lr))
        except Exception:
            pass
    step.sync_to_layer()
    return step


from .manager import CheckpointManager, PreemptionSignal  # noqa: E402,F401
