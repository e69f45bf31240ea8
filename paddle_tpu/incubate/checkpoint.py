"""Elastic auto-checkpoint: preemption-safe epoch loops.

Reference counterpart: incubate/checkpoint/auto_checkpoint.py:71
(AutoCheckpointChecker reads PADDLE_RUNNING_ENV=PADDLE_EDL + HDFS env;
`train_epoch_range` wraps the epoch loop, checkpointing exe+program state
for preemption/resume) and checkpoint_saver.py (versioned dirs). TPU note
(SURVEY §5): preemption handling via checkpoint-restore is how TPU slices
survive maintenance events, so this is first-class here:

    for epoch in acp.train_epoch_range(10):
        train_one_epoch()

On preemption + restart with the same PADDLE_JOB_ID/checkpoint dir, the
range resumes after the last completed epoch.

Crash safety (docs/resilience.md "Elasticity & preemption"): every save on
this path goes through `resilience.CheckpointManager` — data files, then a
checksummed MANIFEST.json, then ONE atomic os.replace() publish. A SIGKILL
landing mid-final-save (the preemption grace window expiring) leaves only a
`.tmp.<pid>` dir that restore never looks at, and a torn/corrupt checkpoint
fails manifest validation and falls back to the newest older complete one
(`resilience.ckpt_fallbacks`). State is collected in the PORTABLE unsharded
format (ZeRO flat buckets split back into per-param views), so a checkpoint
written on an N-wide dp mesh restores on any M-wide one.
"""
from __future__ import annotations

import os
from typing import Iterator, Optional

import numpy as np

from ..framework.program import default_main_program
from ..framework.scope import global_scope
from ..resilience.checkpoint import CheckpointManager, PARAMS_FILE


def _checker_root() -> Optional[str]:
    """Checkpoint dir from the env contract (reference reads
    PADDLE_RUNNING_ENV=PADDLE_EDL + PADDLE_EDL_HDFS_*; local-FS here,
    remote FS mounts look like paths anyway)."""
    if os.environ.get("PADDLE_RUNNING_ENV") not in ("PADDLE_EDL", "LOCAL"):
        return None
    root = os.environ.get("PADDLE_EDL_HDFS_CHECKPOINT_PATH") \
        or os.environ.get("PADDLE_CHECKPOINT_DIR")
    if not root:
        return None
    job = os.environ.get("PADDLE_JOB_ID", "default_job")
    return os.path.join(root, job)


def load_state(path: str) -> dict:
    """Load a checkpoint state file written by `CheckpointSaver` (npz via
    CheckpointManager) or the pre-manager legacy format (.ptck via the
    native threaded IO)."""
    if path.endswith(".ptck"):
        from ..native.ckptio import load_tensors
        return load_tensors(path)
    with np.load(path) as data:
        return {n: data[n] for n in data.files}


class CheckpointSaver:
    """Versioned checkpoint dirs, newest-last, pruned to max_num
    (reference checkpoint_saver.py) — backed by the crash-safe
    `resilience.CheckpointManager` (checksummed manifest + atomic publish
    + fallback past torn checkpoints), so a kill at ANY point during a
    save can never lose the previous complete checkpoint."""

    def __init__(self, root: str, max_num: int = 3):
        self.root = root
        self.max_num = max_num
        self._mgr = CheckpointManager(root, max_keep=max_num)

    def save(self, state: dict, meta: dict) -> int:
        """Publish `state` under the next version (or the step/epoch the
        meta names); returns the version written."""
        versions = self._mgr.steps()
        version = meta.get("step", meta.get("epoch"))
        if version is None:
            version = (versions[-1] + 1) if versions else 0
        version = int(version)
        self._mgr.save(version, arrays=state, meta=meta)
        return version

    def latest(self):
        """(state file path, meta) of the newest COMPLETE checkpoint —
        torn ones (mid-save kill) are skipped with a fallback to the next
        older valid one — or (None, None) when none exists. One
        newest-first walk over BOTH formats: manager dirs (validated
        manifest) and legacy pre-manager dirs (state.ptck + meta.json), so
        a newer legacy checkpoint is never shadowed by an older manager
        one."""
        import json
        from ..resilience.checkpoint import MANIFEST, validate_manifest
        from ..monitor import stat_add
        for v in reversed(self._mgr.steps()):
            path = self._mgr.path(v)
            payload = validate_manifest(path)
            if payload is not None:
                meta = dict(payload.get("meta") or {})
                meta.setdefault("step", int(payload.get("step", v)))
                return os.path.join(path, PARAMS_FILE), meta
            if os.path.exists(os.path.join(path, MANIFEST)):
                stat_add("resilience.ckpt_fallbacks")   # torn manager save
                continue
            state = os.path.join(path, "state.ptck")    # legacy layout
            mpath = os.path.join(path, "meta.json")
            if os.path.exists(state) and os.path.exists(mpath):
                with open(mpath) as f:
                    return state, json.load(f)
        return None, None


def _collect_state(program) -> dict:
    """Persistable scope values in the PORTABLE unsharded checkpoint format
    (`io._portable_arrays`: ZeRO flat bucket entries split back into their
    per-param views), so the resulting checkpoint loads into a replicated
    program directly and repacks into a ZeRO program of ANY dp width via
    `zero.adopt_unsharded_state` on the next dispatch
    (`Executor._resolve_call`)."""
    from ..io import _portable_arrays
    return _portable_arrays(program, global_scope())


def train_epoch_range(max_epoch_num: int, save_checkpoint_inter=None,
                      program=None) -> Iterator[int]:
    """Resumable epoch range (reference auto_checkpoint.py
    train_epoch_range). Without the env contract it degrades to plain
    range()."""
    root = _checker_root()
    program = program or default_main_program()
    if root is None:
        yield from range(max_epoch_num)
        return
    saver = CheckpointSaver(root)
    start = 0
    path, meta = saver.latest()
    if path is not None:
        scope = global_scope()
        for name, arr in load_state(path).items():
            scope.set(name, arr)
        start = int(meta["epoch"]) + 1
    for epoch in range(start, max_epoch_num):
        yield epoch
        saver.save(_collect_state(program), {"epoch": epoch})


class AutoCheckpointChecker:
    """Introspection parity (reference AutoCheckpointChecker)."""

    def __init__(self):
        self.root = _checker_root()

    def get_range_checkpoint_path(self, name=""):
        return self.root

    @property
    def enabled(self):
        return self.root is not None
