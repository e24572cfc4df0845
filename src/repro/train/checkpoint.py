"""Checkpointing: sharded-friendly, atomic, async, elastic.

Layout (one directory per step):

    ckpt_dir/step_000042.tmp-<pid>/   — written here first
        manifest.json                 — tree structure, shapes, dtypes, hashes
        leaf_000000.npy …             — one file per leaf (params + opt state)
    ckpt_dir/step_000042/             — atomic os.rename on completion

Properties the fleet story needs:
  * atomicity      — a crash mid-write never corrupts the latest checkpoint
                     (tmp dir + rename; restore only reads complete dirs)
  * integrity      — per-leaf SHA-256 in the manifest, verified on restore
                     (a silently corrupted disk block fails loudly)
  * async          — save runs on a writer thread off the training loop;
                     `wait()` joins before the next save or process exit
  * elastic        — restore() returns host arrays + the manifest;
                     `restore_sharded` device_puts onto ANY mesh/sharding,
                     so a 512-chip checkpoint restarts on 256 chips (or the
                     CPU tests' 4 fake devices) without conversion
  * gc             — keep_last_k pruning, never removing the newest
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import threading
from pathlib import Path

import jax
import numpy as np


def _flatten_with_paths(tree):
    flat, treedef = jax.tree.flatten_with_path(tree)
    paths = ["/".join(str(k) for k in path) for path, _ in flat]
    leaves = [leaf for _, leaf in flat]
    return paths, leaves, treedef


def _sha(arr: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(arr).tobytes()).hexdigest()[:16]


class CheckpointManager:
    def __init__(self, directory: str | Path, keep_last: int = 3):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.keep_last = keep_last
        self._thread: threading.Thread | None = None

    # -- save ---------------------------------------------------------------
    def save(self, step: int, tree, *, blocking: bool = False) -> None:
        host = jax.tree.map(lambda x: np.asarray(x), tree)
        self.wait()
        if blocking:
            self._write(step, host)
        else:
            self._thread = threading.Thread(
                target=self._write, args=(step, host), daemon=True
            )
            self._thread.start()

    def _write(self, step: int, host_tree) -> None:
        final = self.dir / f"step_{step:09d}"
        tmp = self.dir / f"step_{step:09d}.tmp-{os.getpid()}"
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)
        paths, leaves, _ = _flatten_with_paths(host_tree)
        manifest = {"step": step, "leaves": []}
        for i, (p, leaf) in enumerate(zip(paths, leaves, strict=True)):
            fname = f"leaf_{i:06d}.npy"
            np.save(tmp / fname, leaf)
            manifest["leaves"].append(
                {"path": p, "file": fname, "shape": list(leaf.shape),
                 "dtype": str(leaf.dtype), "sha": _sha(leaf)}
            )
        with open(tmp / "manifest.json", "w") as f:
            json.dump(manifest, f)
        if final.exists():
            shutil.rmtree(final)
        os.rename(tmp, final)
        self._gc()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _gc(self) -> None:
        steps = self.all_steps()
        for s in steps[: -self.keep_last]:
            shutil.rmtree(self.dir / f"step_{s:09d}", ignore_errors=True)

    # -- restore --------------------------------------------------------------
    def all_steps(self) -> list[int]:
        out = []
        for d in self.dir.iterdir():
            if (d.is_dir() and d.name.startswith("step_")
                    and "tmp" not in d.name
                    and (d / "manifest.json").exists()):
                out.append(int(d.name.split("_")[1]))
        return sorted(out)

    def latest_step(self) -> int | None:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, template, step: int | None = None, *, verify: bool = True):
        """Host-array tree matching `template`'s structure."""
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        d = self.dir / f"step_{step:09d}"
        manifest = json.loads((d / "manifest.json").read_text())
        paths, _, treedef = _flatten_with_paths(template)
        by_path = {m["path"]: m for m in manifest["leaves"]}
        leaves = []
        for p in paths:
            m = by_path[p]
            arr = np.load(d / m["file"])
            if verify and _sha(arr) != m["sha"]:
                raise IOError(f"checkpoint corruption detected in {p}")
            leaves.append(arr)
        return jax.tree.unflatten(treedef, leaves), step

    def restore_sharded(self, template, shardings, step: int | None = None):
        """Elastic restore: place onto any mesh via per-leaf device_put."""
        host, step = self.restore(template, step)
        placed = jax.tree.map(
            lambda arr, t, s: jax.device_put(arr.astype(t.dtype), s)
            if s is not None else jax.device_put(arr.astype(t.dtype)),
            host, template, shardings,
        )
        return placed, step
