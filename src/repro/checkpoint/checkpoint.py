"""Sharded, elastic, async checkpointing.

Layout on disk (one directory per step, atomic rename on completion):

    <root>/step_000100.tmp/ -> <root>/step_000100/
        manifest.json       # tree structure, shapes, dtypes, checksums
        shard_p0.npz        # this process's arrays (single flat npz per host)

Elasticity: the manifest stores *logical* array metadata only — restore
targets any mesh: arrays are loaded on host and ``jax.device_put`` with the
*new* mesh's NamedShardings (from the same logical-axis rules), so a run
checkpointed on a 256-chip pod resumes on 512 chips (or 8 CPU devices in the
tests) without a conversion step.

Async: ``save_async`` snapshots to host memory synchronously (cheap) and
writes in a daemon thread; ``wait()`` fences.  A failure mid-write never
corrupts the previous checkpoint (tmp-dir + rename).
"""

from __future__ import annotations

import json
import hashlib
import os
import shutil
import threading
from typing import Any, Dict, Optional

import numpy as np
import jax

__all__ = ["save", "save_async", "restore", "latest_step", "Checkpointer"]

_MANIFEST = "manifest.json"


def _flatten(tree):
    leaves, treedef = jax.tree.flatten(tree)
    return leaves, treedef


def _paths(tree):
    flat, _ = jax.tree.flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
            for path, _ in flat]


def save(root: str, step: int, tree, extra: Optional[Dict[str, Any]] = None):
    """Synchronous checkpoint write with atomic rename."""
    leaves, _ = _flatten(tree)
    names = _paths(tree)
    host = [np.asarray(jax.device_get(x)) for x in leaves]
    final = os.path.join(root, f"step_{step:08d}")
    tmp = final + ".tmp"
    os.makedirs(tmp, exist_ok=True)
    npz_path = os.path.join(tmp, "shard_p0.npz")
    np.savez(npz_path, **{f"a{i}": a for i, a in enumerate(host)})
    digest = hashlib.sha256(open(npz_path, "rb").read()).hexdigest()
    manifest = {
        "step": step,
        "names": names,
        "shapes": [list(a.shape) for a in host],
        "dtypes": [str(a.dtype) for a in host],
        "sha256": digest,
        "extra": extra or {},
    }
    with open(os.path.join(tmp, _MANIFEST), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


def latest_step(root: str) -> Optional[int]:
    if not os.path.isdir(root):
        return None
    steps = [int(d.split("_")[1]) for d in os.listdir(root)
             if d.startswith("step_") and not d.endswith(".tmp")]
    return max(steps) if steps else None


def restore(root: str, step: int, like_tree, shardings=None, verify: bool = True):
    """Load a checkpoint into the structure of ``like_tree``.

    shardings: optional matching pytree of NamedShardings (the *current*
    mesh's) — this is the elastic re-mesh path.  Returns (tree, extra).
    """
    d = os.path.join(root, f"step_{step:08d}")
    manifest = json.load(open(os.path.join(d, _MANIFEST)))
    npz_path = os.path.join(d, "shard_p0.npz")
    if verify:
        digest = hashlib.sha256(open(npz_path, "rb").read()).hexdigest()
        if digest != manifest["sha256"]:
            raise IOError(f"checkpoint {d} corrupt: sha mismatch")
    data = np.load(npz_path)
    leaves, treedef = _flatten(like_tree)
    names = _paths(like_tree)
    if names != manifest["names"]:
        raise ValueError(
            "checkpoint tree mismatch:\n saved: %s...\n want: %s..."
            % (manifest["names"][:4], names[:4]))
    arrays = [data[f"a{i}"] for i in range(len(leaves))]
    if shardings is not None:
        shard_leaves = jax.tree.leaves(shardings)
        out = [jax.device_put(a, s) for a, s in zip(arrays, shard_leaves)]
    else:
        out = [jax.numpy.asarray(a) for a in arrays]
    return jax.tree.unflatten(treedef, out), manifest["extra"]


class Checkpointer:
    """Async wrapper with retention."""

    def __init__(self, root: str, keep: int = 3):
        self.root = root
        self.keep = keep
        self._thread: Optional[threading.Thread] = None
        os.makedirs(root, exist_ok=True)

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def save_async(self, step: int, tree, extra=None):
        self.wait()
        # snapshot to host synchronously so training can mutate buffers
        leaves, treedef = _flatten(tree)
        host = [np.asarray(jax.device_get(x)) for x in leaves]
        snap = jax.tree.unflatten(treedef, host)

        def _write():
            save(self.root, step, snap, extra)
            self._gc()

        self._thread = threading.Thread(target=_write, daemon=True)
        self._thread.start()

    def _gc(self):
        steps = sorted(
            int(d.split("_")[1]) for d in os.listdir(self.root)
            if d.startswith("step_") and not d.endswith(".tmp"))
        for s in steps[: -self.keep]:
            shutil.rmtree(os.path.join(self.root, f"step_{s:08d}"),
                          ignore_errors=True)

    def restore_latest(self, like_tree, shardings=None):
        # a save still being written is the latest checkpoint: let it land
        self.wait()
        step = latest_step(self.root)
        if step is None:
            return None, None, None
        tree, extra = restore(self.root, step, like_tree, shardings)
        return step, tree, extra
