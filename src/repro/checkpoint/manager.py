"""Fault-tolerant checkpointing (DESIGN.md §3.11).

Step-tagged directories with atomic commit (write tmp → fsync → rename), a
MANIFEST for integrity, async save thread, keep-N GC, and *elastic* restore:
arrays are stored as host-global numpy, so a checkpoint written on one mesh
restores onto any other mesh/device-count (the caller re-applies shardings
via jax.device_put).  Interrupted saves are never visible (no MANIFEST ⇒
ignored and GC'd)."""
from __future__ import annotations

import json
import os
import shutil
import threading
import time
from typing import Any

import jax
import numpy as np




def _key_str(k) -> str:
    """Stringify any tree-path key (DictKey .key, SequenceKey .idx,
    GetAttrKey .name)."""
    for attr in ("key", "idx", "name"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def _flatten(tree: Any) -> dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(_key_str(k) for k in path)
        flat[key] = np.asarray(jax.device_get(leaf))
    return flat


def _unflatten_like(example: Any, flat: dict[str, np.ndarray]) -> Any:
    paths, treedef = jax.tree_util.tree_flatten_with_path(example)

    leaves = []
    for path, leaf in paths:
        key = "/".join(_key_str(k) for k in path)
        if key not in flat:
            raise KeyError(f"checkpoint missing leaf {key!r}")
        arr = flat[key]
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(
                f"shape mismatch for {key}: ckpt {arr.shape} vs model {leaf.shape}"
            )
        leaves.append(arr.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3):
        self.dir = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._thread: threading.Thread | None = None

    # -- paths ---------------------------------------------------------------
    def _step_dir(self, step: int) -> str:
        return os.path.join(self.dir, f"step_{step:010d}")

    def steps(self) -> list[int]:
        out = []
        for name in os.listdir(self.dir):
            p = os.path.join(self.dir, name)
            if name.startswith("step_") and os.path.exists(
                os.path.join(p, "MANIFEST.json")
            ):
                out.append(int(name.split("_")[1]))
        return sorted(out)

    def latest_step(self) -> int | None:
        s = self.steps()
        return s[-1] if s else None

    # -- save ----------------------------------------------------------------
    def save(self, step: int, tree: Any, blocking: bool = True, extra: dict | None = None):
        flat = _flatten(tree)  # device_get happens on the caller thread

        def _write():
            final = self._step_dir(step)
            tmp = final + ".tmp"
            if os.path.exists(tmp):
                shutil.rmtree(tmp)
            os.makedirs(tmp)
            np.savez(os.path.join(tmp, "arrays.npz"), **flat)
            manifest = {
                "step": step,
                "time": time.time(),
                "keys": sorted(flat),
                "extra": extra or {},
            }
            with open(os.path.join(tmp, "MANIFEST.json"), "w") as f:
                json.dump(manifest, f)
                f.flush()
                os.fsync(f.fileno())
            if os.path.exists(final):
                shutil.rmtree(final)
            os.rename(tmp, final)
            self._gc()

        if blocking:
            _write()
        else:
            self.wait()
            self._thread = threading.Thread(target=_write, daemon=True)
            self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _gc(self):
        steps = self.steps()
        for s in steps[: -self.keep] if self.keep else []:
            shutil.rmtree(self._step_dir(s), ignore_errors=True)
        # drop orphaned tmp dirs (interrupted saves)
        for name in os.listdir(self.dir):
            if name.endswith(".tmp"):
                shutil.rmtree(os.path.join(self.dir, name), ignore_errors=True)

    # -- restore ---------------------------------------------------------------
    def restore(self, example: Any, step: int | None = None) -> tuple[Any, dict]:
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        d = self._step_dir(step)
        with np.load(os.path.join(d, "arrays.npz")) as z:
            flat = {k: z[k] for k in z.files}
        with open(os.path.join(d, "MANIFEST.json")) as f:
            manifest = json.load(f)
        return _unflatten_like(example, flat), manifest
