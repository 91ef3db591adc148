"""Checkpoint manager: atomic, versioned, async, elastic-restorable.

Layout (one directory per step):

    <root>/step_000123/
        manifest.json            — tree structure, shapes, dtypes, step
        t_000000.npy ...         — one .npy per tensor (gathered global value)
    <root>/LATEST                — atomically updated pointer

Properties engineered for the 1000-node story:
  * atomicity — tensors land in ``step_X.tmp/`` and the directory is
    os.replace()'d into place, then LATEST is swapped; every tensor
    file, the manifest AND the parent directory entry are fsync'd
    before the swap, so a crash (or power cut) mid-write never corrupts
    the previous checkpoint and a completed swap is durable;
  * async — `save(..., blocking=False)` snapshots to host RAM
    (device_get) and writes on a background thread so the solve loop
    only stalls for the device->host copy;
  * elastic restore — tensors are stored as *global* logical arrays, so
    restore just applies the new mesh's NamedSharding (device_put).  At
    real scale the same manifest format shards each tensor into per-host
    files (`shard_spec` records how); restore then uses
    jax.make_array_from_callback so each host reads only its bytes
    (distributed.elastic.from_host_callback).
  * validation + fallback — restore() verifies the manifest against the
    tensor files and the requested tree (shape/dtype/short-read);
    a corrupt or truncated checkpoint raises :class:`CheckpointError`
    and, when the step was implicit (LATEST), restore falls back to the
    previous intact step;
  * keep-k retention that never deletes the step LATEST points at, and
    retry-with-backoff around every filesystem touch
    (:func:`repro.distributed.fault.retry` — shared filesystems hiccup).
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import time
from typing import Any, Optional

import jax
import numpy as np

from .. import telemetry as _telemetry
from ..distributed import fault

__all__ = ["CheckpointError", "CheckpointManager"]


class CheckpointError(ValueError):
    """A checkpoint is unreadable, torn, or inconsistent with the
    requested restore tree."""


def _flatten_with_paths(tree):
    flat, treedef = jax.tree.flatten_with_path(tree)
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)
             for kp, _ in flat]
    vals = [v for _, v in flat]
    return paths, vals, treedef


def _fsync_path(path: str) -> None:
    """fsync a file OR directory entry (durability of the rename)."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


class CheckpointManager:
    def __init__(self, root: str, keep: int = 3, retry_attempts: int = 4,
                 retry_backoff_s: float = 0.05):
        self.root = root
        self.keep = keep
        self.retry_attempts = retry_attempts
        self.retry_backoff_s = retry_backoff_s
        os.makedirs(root, exist_ok=True)
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    def _retry(self, fn):
        return fault.retry(fn, attempts=self.retry_attempts,
                           backoff_s=self.retry_backoff_s)

    def step_dir(self, step: int) -> str:
        return os.path.join(self.root, f"step_{step:09d}")

    # ---------------- save ----------------
    def save(self, step: int, tree: Any, blocking: bool = True,
             extra: Optional[dict] = None) -> None:
        """Write checkpoint ``step``. ``blocking=False`` returns as soon
        as the device->host snapshot completes; the filesystem write runs
        on a daemon thread and any failure surfaces on the next
        ``save``/``wait`` call."""
        self.wait()
        col = _telemetry.get()
        paths, vals, _ = _flatten_with_paths(tree)
        with col.span("checkpoint.snapshot", step=step):
            host_vals = [np.asarray(jax.device_get(v)) for v in vals]
        nbytes = sum(v.nbytes for v in host_vals)

        def write():
            try:   # emitted from the writer thread when async
                with col.span("checkpoint.write", step=step, bytes=nbytes,
                              blocking=blocking):
                    self._write(step, paths, host_vals, extra or {})
            except BaseException as e:  # surfaced on next wait()
                self._error = e
                return
            col.count("checkpoint.saves", 1)
            col.count("checkpoint.bytes_written", nbytes)

        if blocking:
            write()
            self._raise_if_failed()
        else:
            self._thread = threading.Thread(target=write, daemon=True)
            self._thread.start()

    def _write(self, step, paths, host_vals, extra):
        final = self.step_dir(step)
        tmp = final + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        manifest = {"step": step, "time": time.time(), "extra": extra,
                    "tensors": []}
        for i, (p, v) in enumerate(zip(paths, host_vals)):
            fn = f"t_{i:06d}.npy"
            fpath = os.path.join(tmp, fn)

            def write_tensor(fpath=fpath, v=v):
                fault.FaultPlan.active_on_io(fpath)
                with open(fpath, "wb") as f:
                    np.save(f, v)
                    f.flush()
                    os.fsync(f.fileno())

            self._retry(write_tensor)
            manifest["tensors"].append(
                {"path": p, "file": fn, "shape": list(v.shape),
                 "dtype": str(v.dtype), "nbytes": int(v.nbytes)})

        def write_manifest():
            fault.FaultPlan.active_on_io(tmp)
            with open(os.path.join(tmp, "manifest.json"), "w") as f:
                json.dump(manifest, f)
                f.flush()
                os.fsync(f.fileno())

        self._retry(write_manifest)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.replace(tmp, final)
        # durability of the rename itself: fsync the parent dir entry
        # BEFORE LATEST starts pointing at it
        self._retry(lambda: _fsync_path(self.root))
        latest_tmp = os.path.join(self.root, "LATEST.tmp")

        def swap_latest():
            fault.FaultPlan.active_on_io(latest_tmp)
            with open(latest_tmp, "w") as f:
                f.write(os.path.basename(final))
                f.flush()
                os.fsync(f.fileno())
            os.replace(latest_tmp, os.path.join(self.root, "LATEST"))
            _fsync_path(self.root)

        self._retry(swap_latest)
        plan = fault.FaultPlan.active()
        if plan is not None:
            plan.after_save(final)
        self._gc()

    def _gc(self):
        """keep-k retention. The step LATEST points at is never deleted,
        even when a fallback restore moved LATEST behind newer (broken)
        step directories."""
        if self.keep <= 0:
            return
        steps = self.list_steps()
        latest = self._latest_pointer()
        for s in steps[: -self.keep]:
            if s == latest:
                continue
            shutil.rmtree(self.step_dir(s), ignore_errors=True)

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        self._raise_if_failed()

    def _raise_if_failed(self):
        if self._error is not None:
            e, self._error = self._error, None
            raise RuntimeError("async checkpoint write failed") from e

    # ---------------- restore ----------------
    def list_steps(self) -> list[int]:
        out = []
        for d in os.listdir(self.root):
            if d.startswith("step_") and not d.endswith(".tmp"):
                try:
                    out.append(int(d[5:]))
                except ValueError:
                    pass
        return sorted(out)

    def _latest_pointer(self) -> Optional[int]:
        """The step LATEST names (None when absent/dangling)."""
        ptr = os.path.join(self.root, "LATEST")
        if os.path.exists(ptr):
            try:
                with open(ptr) as f:
                    name = f.read().strip()
                if os.path.isdir(os.path.join(self.root, name)):
                    return int(name[5:])
            except (OSError, ValueError):
                return None
        return None

    def latest_step(self) -> Optional[int]:
        step = self._latest_pointer()
        if step is not None:
            return step
        steps = self.list_steps()
        return steps[-1] if steps else None

    def _load_manifest(self, step: int) -> dict:
        d = self.step_dir(step)
        mpath = os.path.join(d, "manifest.json")
        try:
            with open(mpath) as f:
                manifest = json.load(f)
        except FileNotFoundError as e:
            raise CheckpointError(f"step {step}: no manifest at {mpath}") from e
        except (json.JSONDecodeError, OSError) as e:
            raise CheckpointError(f"step {step}: unreadable manifest "
                                  f"({e})") from e
        for key in ("step", "tensors"):
            if key not in manifest:
                raise CheckpointError(f"step {step}: manifest missing "
                                      f"{key!r}")
        return manifest

    def _restore_step(self, step: int, like_tree: Any,
                      shardings: Any = None) -> tuple[Any, dict]:
        d = self.step_dir(step)
        manifest = self._load_manifest(step)
        by_path = {t["path"]: t for t in manifest["tensors"]}
        paths, vals, treedef = _flatten_with_paths(like_tree)
        shard_flat = (treedef.flatten_up_to(shardings)
                      if shardings is not None else [None] * len(vals))
        out = []
        for p, like, sh in zip(paths, vals, shard_flat):
            if p not in by_path:
                raise CheckpointError(
                    f"step {step}: tree leaf {p!r} absent from checkpoint "
                    f"(has {sorted(by_path)})")
            t = by_path[p]
            fpath = os.path.join(d, t["file"])
            try:
                arr = self._retry(lambda fpath=fpath: np.load(fpath))
            except (OSError, ValueError, EOFError) as e:
                raise CheckpointError(
                    f"step {step}: tensor {p!r} unreadable/truncated "
                    f"({t['file']}: {e})") from e
            # torn-storage guard: the bytes on disk must match what the
            # manifest recorded at write time
            if tuple(arr.shape) != tuple(t.get("shape", arr.shape)):
                raise CheckpointError(
                    f"step {step}: tensor {p!r} shape {tuple(arr.shape)} "
                    f"!= manifest {tuple(t['shape'])} (torn write?)")
            if "dtype" in t and str(arr.dtype) != t["dtype"]:
                raise CheckpointError(
                    f"step {step}: tensor {p!r} dtype {arr.dtype} != "
                    f"manifest {t['dtype']}")
            if tuple(arr.shape) != tuple(np.shape(like)):
                raise CheckpointError(
                    f"step {step}: tensor {p!r} shape {tuple(arr.shape)} "
                    f"does not match restore target {tuple(np.shape(like))}"
                    " — wrong grid/config for this checkpoint?")
            arr = arr.astype(np.asarray(like).dtype
                             if not hasattr(like, "dtype") else like.dtype)
            out.append(jax.device_put(arr, sh) if sh is not None else
                       jax.device_put(arr))
        return (treedef.unflatten(out),
                dict(manifest.get("extra") or {}, step=manifest["step"]))

    def restore(self, like_tree: Any, step: Optional[int] = None,
                shardings: Any = None) -> tuple[Any, dict]:
        """Restore into the structure of ``like_tree``; if ``shardings``
        (matching pytree of NamedSharding) is given, place each tensor
        accordingly (elastic restore onto any mesh).

        With ``step=None`` the newest step is used, and a corrupt or
        truncated checkpoint falls back to the previous intact one
        (the torn step is reported in the returned extra dict under
        ``"skipped_corrupt"``). An explicitly requested ``step`` never
        falls back — its :class:`CheckpointError` propagates."""
        if step is not None:
            if not os.path.isdir(self.step_dir(step)):
                raise FileNotFoundError(
                    f"no checkpoint step {step} under {self.root}")
            with _telemetry.get().span("checkpoint.restore", step=step):
                return self._restore_step(step, like_tree, shardings)
        candidates = self.list_steps()
        latest = self.latest_step()
        if latest is None:
            raise FileNotFoundError(f"no checkpoint under {self.root}")
        # newest-first from LATEST (fallback walks strictly older steps)
        candidates = [s for s in reversed(candidates) if s <= latest]
        skipped: list[tuple[int, str]] = []
        col = _telemetry.get()
        for s in candidates:
            try:
                with col.span("checkpoint.restore", step=s):
                    tree, extra = self._restore_step(s, like_tree, shardings)
            except CheckpointError as e:
                skipped.append((s, str(e)))
                col.count("checkpoint.corrupt_skipped", 1)
                continue
            if skipped:
                extra["skipped_corrupt"] = skipped
            if col.enabled:
                col.count("checkpoint.restores", 1)
                col.count("checkpoint.bytes_read",
                          sum(int(getattr(v, "nbytes", 0))
                              for v in jax.tree.leaves(tree)))
            return tree, extra
        raise CheckpointError(
            f"every checkpoint under {self.root} failed validation: "
            + "; ".join(msg for _, msg in skipped))
