"""Fault-tolerant checkpointing and durable-write primitives (DESIGN.md §12.4).

Design points (1000+-node deployments):

* **Logical layout, not device layout** — checkpoints store flat arrays plus
  the pytree structure and the *PartitionSpec* strings.  Restore re-shards to
  whatever mesh the job comes back with (elastic re-shard: a 512-chip job can
  resume on 256 chips).
* **Atomicity** — writes go to ``<name>.tmp/`` and are renamed only after the
  manifest fsyncs; a crash mid-write never corrupts the latest checkpoint.
  The write/rename/retention primitives (``fsync_json``, ``replace_dir``,
  ``retain_latest``, ``latest_numbered``) are shared with the durable index
  store (``index/store.py``, DESIGN.md §12), so both subsystems have ONE
  crash-safety story.
* **Double buffering / retention** — keep the last ``keep`` checkpoints;
  deletion only after a newer one is durable.
* **Async** — ``save_async`` snapshots to host memory (device_get) on the
  training thread, then writes on a background thread so the step loop only
  blocks for the copy, not the I/O.
* **Data-pipeline state** — the sampler/shard cursor is part of the payload,
  so restarts are bit-identical (no skipped or repeated batches).

Exactness contract: ``restore_checkpoint(save_checkpoint(payload))`` returns
arrays bit-identical to the saved host copies; restarts resume the data
pipeline bit-identically (no skipped or repeated batches).
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
from pathlib import Path
from typing import Any, Callable

import numpy as np

# jax is imported only inside the pytree functions: the index store and the
# bulk-ingest spawn workers import this module and stay numpy-only, so a
# worker never loads a JAX backend next to the process that holds the chip.

__all__ = [
    "save_checkpoint",
    "restore_checkpoint",
    "CheckpointManager",
    "fsync_json",
    "replace_dir",
    "retain_latest",
    "latest_numbered",
]

_MANIFEST = "manifest.json"


# ---------------------------------------------------------------------------
# durable-write primitives (shared with index/store.py — DESIGN.md §12.4)
# ---------------------------------------------------------------------------


def fsync_json(path: str | Path, obj: Any) -> None:
    """Dump ``obj`` as JSON and fsync before returning (DESIGN.md §12.4).

    The manifest fsync is the durability point of every atomic directory
    write: once it returns, a rename of the enclosing directory publishes a
    complete, self-consistent artifact.
    """
    with open(path, "w") as f:
        json.dump(obj, f)
        f.flush()
        os.fsync(f.fileno())


def append_durable(path: str | Path, data: bytes) -> int:
    """Append ``data`` to ``path`` and fsync before returning (DESIGN.md
    §12.4 / §18.1) — the durability point of every write-ahead log frame:
    once this returns, the bytes survive any crash, so an operation logged
    through it may be acknowledged.  Returns the byte offset the frame was
    written at (the file length before the append).  The file is opened and
    closed per call so crashed writers never hold a recovered-over handle.
    The appended bytes are identical to ``data`` (framing/CRC is the
    caller's job — see ``index/wal.py``)."""
    with open(path, "ab") as f:
        offset = f.tell()
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    return offset


def replace_dir(tmp: str | Path, final: str | Path) -> None:
    """Publish ``tmp`` as ``final`` without ever exposing a partial artifact
    (DESIGN.md §12.4).

    Directories cannot be renamed over on POSIX, so an existing ``final``
    is first renamed aside to ``<final>.old`` (atomic), then ``tmp`` is
    renamed into place (atomic), then the old copy is deleted.  No reader
    ever sees a half-written directory under the final name; a crash
    between the two renames loses only the *name* — the complete previous
    artifact survives as ``<final>.old`` (and numbered readers like
    ``latest_numbered`` simply fall back to the previous entry).
    """
    tmp, final = Path(tmp), Path(final)
    old = final.with_name(final.name + ".old")
    if old.exists():
        shutil.rmtree(old)
    had_old = False
    if final.exists():
        final.rename(old)
        had_old = True
    tmp.rename(final)
    if had_old:
        shutil.rmtree(old, ignore_errors=True)


def retain_latest(directory: str | Path, prefix: str, keep: int) -> None:
    """Delete all but the ``keep`` highest-numbered ``<prefix>_<N>`` dirs
    (DESIGN.md §12.4 retention; ``keep <= 0`` retains everything)."""
    if keep <= 0:
        return
    entries = sorted(_numbered(Path(directory), prefix))
    for _, p in entries[:-keep]:
        shutil.rmtree(p, ignore_errors=True)


def latest_numbered(directory: str | Path, prefix: str) -> int | None:
    """Highest N among complete ``<prefix>_<N>`` dirs — complete means the
    manifest exists, i.e. the §12.4 rename happened (``None`` if none)."""
    entries = _numbered(Path(directory), prefix)
    return max((n for n, _ in entries), default=None)


def _numbered(directory: Path, prefix: str) -> list[tuple[int, Path]]:
    out: list[tuple[int, Path]] = []
    for p in directory.glob(f"{prefix}_*"):
        if not p.is_dir() or p.name.endswith(".tmp"):
            continue
        if not (p / _MANIFEST).exists():
            continue
        try:
            out.append((int(p.name.rsplit("_", 1)[1]), p))
        except ValueError:
            continue
    return out


def _flatten(tree: Any) -> tuple[list[np.ndarray], Any]:
    import jax

    leaves, treedef = jax.tree.flatten(tree)
    return [np.asarray(l) for l in leaves], treedef


def save_checkpoint(directory: str | Path, step: int, payload: Any, keep: int = 3) -> Path:
    """Atomic synchronous save of an arbitrary pytree ``payload``
    (DESIGN.md §12.4 write protocol: tmp dir -> manifest fsync -> rename)."""
    directory = Path(directory)
    directory.mkdir(parents=True, exist_ok=True)
    tmp = directory / f"step_{step}.tmp"
    final = directory / f"step_{step}"
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir()
    leaves, treedef = _flatten(payload)
    np.savez(tmp / "arrays.npz", **{f"a{i}": l for i, l in enumerate(leaves)})
    meta = {
        "step": step,
        "n_leaves": len(leaves),
        "treedef": str(treedef),
        "time": time.time(),
        "leaf_shapes": [list(l.shape) for l in leaves],
        "leaf_dtypes": [str(l.dtype) for l in leaves],
    }
    fsync_json(tmp / _MANIFEST, meta)
    replace_dir(tmp, final)
    retain_latest(directory, "step", keep)
    return final


def latest_step(directory: str | Path) -> int | None:
    """Highest durable checkpoint step in ``directory`` (DESIGN.md §12.4)."""
    return latest_numbered(directory, "step")


def restore_checkpoint(
    directory: str | Path,
    template: Any,
    step: int | None = None,
    shardings: Any | None = None,
) -> tuple[Any, int] | None:
    """Restore into the structure of ``template``; optionally re-shard with
    ``shardings`` (a pytree of NamedSharding for the *current* mesh —
    elastic resume, DESIGN.md §12.4)."""
    import jax

    directory = Path(directory)
    step = step if step is not None else latest_step(directory)
    if step is None:
        return None
    path = directory / f"step_{step}"
    with np.load(path / "arrays.npz") as z:
        arrays = [z[f"a{i}"] for i in range(len(z.files))]
    _, treedef = jax.tree.flatten(template)
    restored = jax.tree.unflatten(treedef, arrays)
    if shardings is not None:
        restored = jax.tree.map(
            lambda a, s: jax.device_put(a, s) if s is not None else jax.device_put(a),
            restored,
            shardings,
        )
    return restored, step


class CheckpointManager:
    """Async double-buffered manager with restart-counter bookkeeping
    (DESIGN.md §12.4: one write in flight, errors surfaced on ``wait``)."""

    def __init__(self, directory: str | Path, keep: int = 3):
        self.directory = Path(directory)
        self.keep = keep
        self._thread: threading.Thread | None = None
        self._error: BaseException | None = None

    def save_async(self, step: int, payload: Any) -> None:
        import jax

        self.wait()  # one in flight at a time (double buffering)
        host = jax.tree.map(np.asarray, jax.device_get(payload))

        def _write():
            try:
                save_checkpoint(self.directory, step, host, keep=self.keep)
            except BaseException as e:  # surfaced on next wait()
                self._error = e

        self._thread = threading.Thread(target=_write, daemon=True)
        self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def restore_latest(self, template: Any, shardings: Any | None = None):
        return restore_checkpoint(self.directory, template, shardings=shardings)
