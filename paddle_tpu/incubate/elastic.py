"""Preemption handling + elastic (slice-resize) resume.

Reference counterparts: incubate/checkpoint/auto_checkpoint.py (epoch-range
resume; this module adds STEP-level preemption), fleet elastic scaling
(reference handles trainer loss via PS heartbeats —
distributed/gloo + kvstore heartbeats cover detection here).

TPU-native story (SURVEY §5): TPU slices are preempted with a SIGTERM
notice (maintenance events, spot reclaim). `PreemptionGuard` converts that
notice into a final checkpoint + clean exit; on restart
`steps()`/`train_epoch_range` resume after the last completed step. Resume
is ELASTIC in two layers:

* checkpoints hold full (unsharded) host arrays, and the executor's GSPMD
  `in_shardings` re-shard them on the first dispatch, so a job
  checkpointed on a dp=4 mesh restarts unchanged on dp=2 (or any other
  layout) — re-sharding is the compiler's job, not the checkpoint's;
* ZeRO flat-bucket state (parallel/zero.py) is saved as its per-param
  views and REPACKED for the restoring program's own dp width by
  `zero.adopt_unsharded_state`, which the executor's call resolver
  (`Executor._resolve_call`) runs on the first post-restore dispatch or
  inspection, so sharded optimizer/gradient/parameter
  storage survives a train-on-N / resume-on-M resize bit-for-bit. A dp
  the 64-element bucket padding does not divide takes the full-width
  replicated fallback, counted under `executor.zero_manual_fallbacks`.

Saves go through `resilience.CheckpointManager` (checksummed manifest +
atomic publish): a SIGKILL past the grace window mid-final-save leaves only
a `.tmp` dir and restore falls back to the last complete checkpoint.
Tests: tests/test_elastic.py; drill: scripts/chaos_smoke.py
--preemption-drill.
"""
from __future__ import annotations

import signal
import threading
from typing import Iterator

from ..framework.program import default_main_program
from ..framework.scope import global_scope
from .checkpoint import CheckpointSaver, _collect_state, load_state


class PreemptionGuard:
    """Install once near the top of the trainer; iterate `steps()`.

        guard = PreemptionGuard("/ckpts/job7", program=main)
        for step in guard.steps(10_000, save_interval=200):
            exe.run(...)

    On SIGTERM (or SIGUSR1 — some schedulers use it for the early notice)
    the CURRENT step finishes, a final checkpoint is written, and steps()
    raises SystemExit(143) so the process exits before the hard kill.
    Restart with the same directory resumes after the last completed step.

    The guard also works as a context manager; leaving the `with` block
    (or calling `uninstall()`) restores whatever SIGTERM/SIGUSR1 handlers
    were installed before it, so guards never leak handlers across
    trainers or tests.
    """

    _SIGNALS = (signal.SIGTERM, signal.SIGUSR1)

    def __init__(self, ckpt_dir: str, program=None, max_num: int = 3,
                 exit_on_preempt: bool = True):
        self.program = program
        self.saver = CheckpointSaver(ckpt_dir, max_num=max_num)
        self.exit_on_preempt = exit_on_preempt
        self.preempted = threading.Event()
        self._prev = {}
        if threading.current_thread() is threading.main_thread():
            for sig in self._SIGNALS:
                try:
                    self._prev[sig] = signal.signal(sig, self._on_signal)
                except (ValueError, OSError):  # restricted env
                    pass

    def _on_signal(self, signum, frame):
        self.preempted.set()
        prev = self._prev.get(signum)
        if callable(prev):
            prev(signum, frame)

    def uninstall(self) -> None:
        """Restore the SIGTERM/SIGUSR1 handlers that were active before
        this guard installed its own. Idempotent; a no-op off the main
        thread (where nothing was installed)."""
        for sig, prev in list(self._prev.items()):
            try:
                if signal.getsignal(sig) == self._on_signal:
                    signal.signal(sig, prev)
            except (ValueError, OSError):
                pass
            self._prev.pop(sig, None)

    def __enter__(self) -> "PreemptionGuard":
        return self

    def __exit__(self, *exc) -> bool:
        self.uninstall()
        return False

    # -- checkpoint plumbing -------------------------------------------------
    def checkpoint_now(self, step: int) -> int:
        program = self.program or default_main_program()
        return self.saver.save(_collect_state(program), {"step": step})

    def restore(self) -> int:
        """Load the newest COMPLETE checkpoint into the global scope (torn
        mid-save checkpoints fall back to the previous one); returns the
        next step to run (0 if none)."""
        path, meta = self.saver.latest()
        if path is None:
            return 0
        scope = global_scope()
        for name, arr in load_state(path).items():
            scope.set(name, arr)
        return int(meta["step"]) + 1

    # -- the resumable loop --------------------------------------------------
    def steps(self, total: int, save_interval: int = 100) -> Iterator[int]:
        start = self.restore()
        for step in range(start, total):
            yield step
            last = step == total - 1
            if self.preempted.is_set() or last \
                    or (step + 1) % save_interval == 0:
                self.checkpoint_now(step)
            if self.preempted.is_set() and not last:
                if self.exit_on_preempt:
                    raise SystemExit(143)   # 128 + SIGTERM, like a clean kill
                return
