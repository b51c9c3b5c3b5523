"""Checkpointing for fault tolerance + elastic restarts.

Design points (1000+-node posture):
  * **atomic**: write to ``step_XXXX.tmp`` then ``os.replace`` — a crash
    mid-write can never corrupt the latest checkpoint.
  * **mesh-shape agnostic**: every param/optimizer leaf is saved as a full
    (unsharded) array keyed by its pytree path; on load the launcher
    re-applies the current mesh's shardings, so restart on a different
    data-parallel extent works (elastic scaling).  On a real fleet the same
    layout is written per-shard with a process-0 manifest; the gather is
    the CPU-container simplification and is isolated in ``_to_host``.
  * **self-describing**: a JSON manifest stores step, data-pipeline state,
    config fingerprint, leaf dtypes/shapes for validation, and the shard
    **topology** the run trained under — restoring onto a different
    topology raises ``TopologyMismatch`` pointing at the sanctioned path
    (``GraphRuntime.rescale`` / ``rescale_checkpoint``) instead of failing
    deep in shape or batch-source mismatches (docs/elastic.md).
  * **crash-safe open**: stale ``step_*.tmp`` directories left by a write
    interrupted mid-flight are swept on open; ``list_steps`` additionally
    requires a manifest, so a half-written checkpoint is never resumable.
  * **async**: `save` can hand off to a background thread (double-buffered;
    at most one outstanding write) so the step loop is not blocked.
  * **retention**: keep the newest ``keep`` checkpoints, always retaining
    step-aligned "anchors" (every ``anchor_every`` steps).
"""

from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np


def _flatten(tree) -> Dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        flat[key] = np.asarray(jax.device_get(leaf))
    return flat


def _unflatten_into(tree, flat: Dict[str, np.ndarray]):
    def rebuild(path, leaf):
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        if key not in flat:
            raise KeyError(f"checkpoint missing leaf {key}")
        arr = flat[key]
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(f"shape mismatch for {key}: ckpt {arr.shape} vs model {leaf.shape}")
        return arr
    return jax.tree_util.tree_map_with_path(rebuild, tree)


class TopologyMismatch(ValueError):
    """A checkpoint written under one shard topology was asked to restore
    under a different one.  Raised loudly at restore time — the fix is the
    sanctioned exact-rescale path, never a silent reinterpretation."""


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3, anchor_every: int = 0,
                 async_save: bool = True):
        self.dir = directory
        self.keep = keep
        self.anchor_every = anchor_every
        self.async_save = async_save
        self._thread: Optional[threading.Thread] = None
        os.makedirs(directory, exist_ok=True)
        # crash-safe open: a write interrupted mid-flight leaves a step_*.tmp
        # directory behind; it is dead weight (never listed, never restored)
        # and would shadow a later write of the same step, so sweep it now
        for name in os.listdir(directory):
            if name.startswith("step_") and name.endswith(".tmp"):
                shutil.rmtree(os.path.join(directory, name), ignore_errors=True)

    # -- save -----------------------------------------------------------
    def save(self, step: int, state: Any, extra: Optional[Dict] = None,
             topology: Optional[Dict] = None) -> str:
        """state: any pytree (params + optimizer + rng); extra: JSON-able
        (data-pipeline state, config fingerprint); topology: JSON-able shard
        layout descriptor (e.g. ``{"n_shards": 4, "batch_size": 64}``) that
        ``restore(expect_topology=...)`` validates before touching arrays."""
        flat = _flatten(state)   # device_get on the step thread: cheap on CPU,
                                 # on TPU this is the D2H copy we double-buffer
        if self._thread is not None:
            self._thread.join()  # at most one outstanding write
        if self.async_save:
            self._thread = threading.Thread(
                target=self._write, args=(step, flat, extra or {}, topology),
                daemon=True)
            self._thread.start()
        else:
            self._write(step, flat, extra or {}, topology)
        return self._path(step)

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _path(self, step: int) -> str:
        return os.path.join(self.dir, f"step_{step:010d}")

    def _write(self, step: int, flat: Dict[str, np.ndarray], extra: Dict,
               topology: Optional[Dict] = None):
        final = self._path(step)
        tmp = final + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        with open(os.path.join(tmp, "arrays.npz"), "wb") as f:
            np.savez(f, **flat)
            f.flush()
            os.fsync(f.fileno())
        manifest = {
            "step": step,
            "extra": extra,
            "topology": topology,
            "leaves": {k: {"shape": list(v.shape), "dtype": str(v.dtype)}
                       for k, v in flat.items()},
        }
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        if os.path.exists(final):
            shutil.rmtree(final)
        os.replace(tmp, final)   # atomic publish
        self._gc()

    def _gc(self):
        steps = self.list_steps()
        if len(steps) <= self.keep:
            return
        doomed = steps[: -self.keep]
        for s in doomed:
            if self.anchor_every and s % self.anchor_every == 0:
                continue
            shutil.rmtree(self._path(s), ignore_errors=True)

    # -- load -----------------------------------------------------------
    def list_steps(self):
        steps = []
        for name in os.listdir(self.dir):
            if name.startswith("step_") and not name.endswith(".tmp"):
                if os.path.exists(os.path.join(self.dir, name, "manifest.json")):
                    steps.append(int(name.split("_")[1]))
        return sorted(steps)

    def latest_step(self) -> Optional[int]:
        steps = self.list_steps()
        return steps[-1] if steps else None

    def restore(self, step: int, state_template: Any,
                expect_topology: Optional[Dict] = None) -> Tuple[Any, Dict]:
        """Returns (state, extra).  ``state_template`` supplies the pytree
        structure + shapes (abstract or concrete); arrays are loaded and may
        be re-sharded by the caller (device_put with current shardings).

        ``expect_topology`` (when given) is compared against the manifest's
        recorded topology *before* any array is touched; a mismatch raises
        ``TopologyMismatch``.  Manifests written before topology stamping
        (no ``topology`` key / ``None``) pass unconditionally."""
        path = self._path(step)
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        saved = manifest.get("topology")
        if expect_topology is not None and saved is not None and saved != expect_topology:
            raise TopologyMismatch(
                f"checkpoint at step {step} was written under topology {saved} "
                f"but the current run expects {expect_topology}.  Resuming "
                f"across shard topologies silently is never correct — use the "
                f"exact-rescale path (GraphRuntime.rescale / "
                f"GraphRuntime.rescale_checkpoint, see docs/elastic.md) to "
                f"remap the owner partition and sampler state first.")
        with np.load(os.path.join(path, "arrays.npz")) as z:
            flat = {k: z[k] for k in z.files}
        state = _unflatten_into(state_template, flat)
        return state, manifest["extra"]

    def read_extra(self, step: Optional[int] = None) -> Optional[Dict]:
        """Read just the ``extra`` manifest of a checkpoint (latest by
        default) without touching the arrays — enough to recover e.g. the
        runtime spec before any state template exists.  None if the
        directory holds no checkpoint."""
        step = self.latest_step() if step is None else step
        if step is None:
            return None
        with open(os.path.join(self._path(step), "manifest.json")) as f:
            return json.load(f)["extra"]

    def restore_latest(self, state_template: Any,
                       expect_topology: Optional[Dict] = None,
                       ) -> Optional[Tuple[int, Any, Dict]]:
        step = self.latest_step()
        if step is None:
            return None
        state, extra = self.restore(step, state_template,
                                    expect_topology=expect_topology)
        return step, state, extra
