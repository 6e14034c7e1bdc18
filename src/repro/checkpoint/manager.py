"""Checkpointing: atomic pytree save/restore with an async writer.

No orbax in this environment, so this is a small self-contained implementation
with the properties a 1000-node run needs from the *per-process* layer:

  * atomic publish (write to tmp dir, fsync, rename) — a crash mid-write can
    never corrupt the latest checkpoint;
  * async mode: the device->host copy happens synchronously (cheap), the disk
    write happens on a background thread so the simulation overlaps I/O;
  * retention (`keep`) + monotonically named steps + `latest_step()`;
  * layout: one .npz per save with path-keyed arrays + a JSON manifest
    (dtypes/shapes/step) used for validation on restore.

Probe interaction (DESIGN.md §12): core/probes.ProbeState is an ordinary
pytree (NamedTuple holding a dict of chunk buffers), so checkpointing a
probed run is just `save((state, probe_state), step)` with a matching
(state, probe_state) template on restore — the path keys below handle both
NamedTuple fields (SequenceKey.idx) and the buffer dict (DictKey.key).  A
restore mid-chunk resumes recording at the saved cursor; the chunk files
`probes.simulate_chunked` re-flushes after restore overwrite (not
duplicate) the partial ones, because files are named by their first
recorded step.
"""
from __future__ import annotations

import json
import os
import queue
import shutil
import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import jax

PyTree = Any
_SEP = "|"


def _flatten(tree: PyTree) -> Tuple[Dict[str, np.ndarray], Dict[str, str]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out, dtypes = {}, {}
    for path, leaf in flat:
        key = _SEP.join(str(getattr(p, 'key', getattr(p, 'idx', p)))
                        for p in path)
        arr = np.asarray(leaf)
        dtypes[key] = arr.dtype.name
        if arr.dtype.name == "bfloat16":      # numpy .npz can't store bf16
            arr = arr.view(np.uint16)
        out[key] = arr
    return out, dtypes


def save_pytree(tree: PyTree, directory: str, step: int) -> str:
    """Synchronous atomic save; returns the published path."""
    os.makedirs(directory, exist_ok=True)
    tmp = os.path.join(directory, f".tmp_step_{step:09d}")
    final = os.path.join(directory, f"step_{step:09d}")
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    arrays, dtypes = _flatten(tree)
    np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
    manifest = {"step": step,
                "arrays": {k: {"shape": list(v.shape), "dtype": dtypes[k]}
                           for k, v in arrays.items()}}
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


def restore_pytree(template: PyTree, directory: str,
                   step: Optional[int] = None) -> Tuple[PyTree, int]:
    """Restore into the structure/dtypes of `template`."""
    step = latest_step(directory) if step is None else step
    if step is None:
        raise FileNotFoundError(f"no checkpoint in {directory}")
    path = os.path.join(directory, f"step_{step:09d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    data = np.load(os.path.join(path, "arrays.npz"))
    flat = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for kpath, leaf in flat[0]:
        key = _SEP.join(str(getattr(p, 'key', getattr(p, 'idx', p)))
                        for p in kpath)
        if key not in manifest["arrays"]:
            raise KeyError(f"checkpoint missing {key}")
        arr = data[key]
        if manifest["arrays"][key]["dtype"] == "bfloat16":
            arr = arr.view(jax.numpy.bfloat16.dtype)
        if list(arr.shape) != list(leaf.shape):
            raise ValueError(f"shape mismatch for {key}: "
                             f"{arr.shape} vs {leaf.shape}")
        leaves.append(jax.numpy.asarray(arr, dtype=leaf.dtype))
    return jax.tree_util.tree_unflatten(flat[1], leaves), manifest["step"]


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = [int(d.split("_")[1]) for d in os.listdir(directory)
             if d.startswith("step_")]
    return max(steps) if steps else None


class CheckpointManager:
    """Retention + optional async writer thread."""

    def __init__(self, directory: str, keep: int = 3,
                 async_save: bool = True):
        self.directory = directory
        self.keep = keep
        self.async_save = async_save
        self._q: "queue.Queue" = queue.Queue(maxsize=2)
        self._errors: List[BaseException] = []
        self._thread: Optional[threading.Thread] = None
        if async_save:
            self._thread = threading.Thread(target=self._worker, daemon=True)
            self._thread.start()

    def _worker(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            tree, step = item
            try:
                save_pytree(tree, self.directory, step)
                self._gc()
            except BaseException as e:     # surfaced on next save()/close()
                self._errors.append(e)
            finally:
                self._q.task_done()

    def _gc(self):
        steps = sorted(s for s in (int(d.split("_")[1])
                                   for d in os.listdir(self.directory)
                                   if d.startswith("step_")))
        for s in steps[:-self.keep] if self.keep else []:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:09d}"),
                          ignore_errors=True)

    def save(self, tree: PyTree, step: int):
        if self._errors:
            raise self._errors.pop()
        host_tree = jax.tree.map(np.asarray, tree)   # sync device->host
        if self.async_save:
            self._q.put((host_tree, step))
        else:
            save_pytree(host_tree, self.directory, step)
            self._gc()

    def wait(self):
        if self.async_save:
            self._q.join()
        if self._errors:
            raise self._errors.pop()

    def restore(self, template: PyTree, step: Optional[int] = None):
        return restore_pytree(template, self.directory, step)

    def close(self):
        if self._thread is not None:
            self._q.put(None)
            self._thread.join()
            self._thread = None
        if self._errors:
            raise self._errors.pop()
