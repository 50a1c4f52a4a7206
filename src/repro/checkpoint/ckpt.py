"""Checkpointing of pytrees, with sync and async writes.

Layout: one directory per step containing

* ``manifest.json`` — step, per-leaf shape/dtype and caller extras;
* ``arrays.npz`` — *logical* (unsharded) array values.

Saving gathers each leaf to host (addressable shards -> logical array) —
correct on any mesh. :class:`repro.serve.CounterfactualService` persists its
log, carries and cache through :func:`save_checkpoint` /
:func:`restore_checkpoint`. :class:`AsyncCheckpointer` writes on a
background thread (the caller goes on while the previous step serialises),
and ``wait()`` gives a barrier for tests/shutdown.
"""
from __future__ import annotations

import dataclasses
import json
import queue
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Tree = Any


def _flatten(tree: Tree) -> Tuple[List[Tuple[str, Any]], Any]:
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        key = "/".join(_path_str(p) for p in path)
        out.append((key, leaf))
    return out, treedef


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    if hasattr(p, "name"):
        return str(p.name)
    return str(p)


def save_checkpoint(path: str | Path, step: int, tree: Tree,
                    extra: Optional[Dict[str, Any]] = None) -> Path:
    """Synchronous save. Returns the checkpoint directory."""
    root = Path(path)
    ckpt_dir = root / f"step_{step:08d}"
    tmp_dir = root / f".tmp_step_{step:08d}"
    tmp_dir.mkdir(parents=True, exist_ok=True)

    flat, _ = _flatten(tree)
    arrays = {}
    manifest = {"step": step, "leaves": {}, "extra": extra or {},
                "time": time.time()}
    for key, leaf in flat:
        arr = np.asarray(jax.device_get(leaf))
        arrays[key] = arr
        manifest["leaves"][key] = {"shape": list(arr.shape),
                                   "dtype": str(arr.dtype)}
    np.savez(tmp_dir / "arrays.npz", **arrays)
    (tmp_dir / "manifest.json").write_text(json.dumps(manifest))
    # atomic publish: rename tmp -> final (crash-safe: partial writes never
    # appear under step_*)
    if ckpt_dir.exists():
        import shutil
        shutil.rmtree(ckpt_dir)
    tmp_dir.rename(ckpt_dir)
    return ckpt_dir


def latest_step(path: str | Path) -> Optional[int]:
    root = Path(path)
    if not root.exists():
        return None
    steps = [int(p.name.split("_")[1]) for p in root.glob("step_*")]
    return max(steps) if steps else None


def restore_checkpoint(path: str | Path, like: Tree,
                       step: Optional[int] = None) -> Tuple[Tree, Dict]:
    """Restore into the structure of ``like`` (values ignored), leaves on
    the default device."""
    root = Path(path)
    if step is None:
        step = latest_step(root)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {root}")
    ckpt_dir = root / f"step_{step:08d}"
    manifest = json.loads((ckpt_dir / "manifest.json").read_text())
    data = np.load(ckpt_dir / "arrays.npz")

    flat, treedef = _flatten(like)
    leaves = []
    for key, _ in flat:
        if key not in data:
            raise KeyError(f"checkpoint missing leaf {key}")
        leaves.append(jnp.asarray(data[key]))
    tree = jax.tree_util.tree_unflatten(treedef, leaves)
    return tree, manifest


@dataclasses.dataclass
class AsyncCheckpointer:
    """Background-thread checkpoint writer with at-most-one queued save."""
    path: str | Path
    keep: int = 3

    def __post_init__(self):
        self._q: "queue.Queue" = queue.Queue(maxsize=1)
        self._err: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()
        self._pending = 0
        self._lock = threading.Lock()

    def _worker(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            step, host_tree, extra = item
            try:
                save_checkpoint(self.path, step, host_tree, extra)
                self._gc()
            except BaseException as e:   # surfaced on next save()/wait()
                self._err = e
            finally:
                with self._lock:
                    self._pending -= 1

    def _gc(self):
        root = Path(self.path)
        steps = sorted(root.glob("step_*"))
        for old in steps[: max(0, len(steps) - self.keep)]:
            import shutil
            shutil.rmtree(old, ignore_errors=True)

    def save(self, step: int, tree: Tree,
             extra: Optional[Dict[str, Any]] = None):
        """Device->host copy happens here (blocking); serialization doesn't."""
        if self._err:
            err, self._err = self._err, None
            raise err
        host_tree = jax.tree.map(lambda x: np.asarray(jax.device_get(x)),
                                 tree)
        with self._lock:
            self._pending += 1
        self._q.put((step, host_tree, extra))

    def wait(self, timeout: float = 60.0):
        t0 = time.time()
        while True:
            with self._lock:
                if self._pending == 0:
                    break
            if time.time() - t0 > timeout:
                raise TimeoutError("checkpoint writer stuck")
            time.sleep(0.01)
        if self._err:
            err, self._err = self._err, None
            raise err

    def close(self):
        self.wait()
        self._q.put(None)
        self._thread.join(timeout=10)
