"""Async in-memory snapshots + peer replication: just-in-time checkpointing.

The disk `CheckpointManager` chain bounds a restart's loss to one
checkpoint interval plus a cold restore. This module tightens that bound
to one SNAPSHOT interval (`FLAGS_snapshot_steps`, typically a few steps)
by keeping a double-buffered device->host copy of the portable training
state in memory and flushing it to disk only when the process is about to
die (SIGTERM inside the launcher-exported `PADDLE_LAUNCH_GRACE_S`):

* **Capture is off the hot path.** The executor hands the capture worker
  async DEVICE COPIES of the step's freshly-adopted state arrays (a bare
  reference would die when the next step DONATES the buffer into its XLA
  call) and returns; a single daemon thread materializes them host-side (`io._portable_arrays`, the
  same portable-unsharded collector checkpoints use — ZeRO flat buckets
  split into per-param views, `__rng_state__` included) into the standby
  buffer and atomically swaps it live. The main thread never blocks on
  device readiness; an interval so short that a capture is still in
  flight skips (counted, `resilience.snapshot_skips`).
* **Double buffering** means `latest()` is always a COMPLETE snapshot:
  the worker fills the standby buffer and swaps the newest pointer only
  after the copy finished, so a SIGTERM mid-capture flushes the previous
  complete snapshot, never a torn one.
* **Peer replication** (`replicate`): each rank ships its newest snapshot
  to its ring buddy (rank+1 mod world) over the gloo host transport, so a
  lost host's state — ZeRO shards included, in portable form — survives
  on a peer. One all-gather round moves every rank's payload; each rank
  RETAINS only its buddy's (memory stays O(2 snapshots/rank)).
* **Flush** writes the newest own snapshot AND the held peer payload
  through `CheckpointManager` (checksummed manifest + atomic publish), so
  a SIGKILL past the grace window mid-flush leaves the previous complete
  flush intact — the SIGTERM-during-snapshot contract is the checkpoint
  contract, inherited, and tested the same way (fault site 'ckpt.write').
* **Recovery ladder** (`recover`): peer snapshot -> local snapshot ->
  disk CheckpointManager, newest valid rung wins; the chosen rung is
  stamped into `<dir>/recovery_rank<r>.json` for the gang supervisor's
  log (distributed/launch.py prints it after the gang exits).

Executor wiring: `FLAGS_snapshot_steps > 0` makes every Executor call
`maybe_capture` after its state writeback (framework/executor.py);
`snapshot_dir()` resolves FLAGS_snapshot_dir -> PADDLE_SNAPSHOT_DIR (the
gang-shared dir the launch supervisor exports) -> a temp dir.

Stats: resilience.snapshots / snapshot_ms / snapshot_skips /
snapshot_flushes / peer_replications. Tests: tests/test_snapshot.py;
drill: scripts/chaos_smoke.py --integrity-drill (docs/resilience.md
"Snapshots & integrity").
"""
from __future__ import annotations

import json
import os
import signal
import tempfile
import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

from ..observability import metrics as _metrics
from ..observability import trace as _trace
from .checkpoint import CheckpointManager

RNG_KEY = "__rng_state__"


def rng_to_host(key) -> np.ndarray:
    """Typed jax PRNG key -> plain uint32 host array (np.asarray refuses
    typed keys). Already-plain arrays (a restored snapshot's payload)
    pass through."""
    import jax
    if hasattr(key, "dtype") and jax.dtypes.issubdtype(key.dtype,
                                                       jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return np.asarray(key)


def rng_from_host(data):
    """Inverse of rng_to_host: host uint32 words -> a typed key of the
    default PRNG impl (the impl jax.random.key / paddle.seed used)."""
    import jax
    if hasattr(data, "dtype") and jax.dtypes.issubdtype(data.dtype,
                                                        jax.dtypes.prng_key):
        return data
    return jax.random.wrap_key_data(np.asarray(data))


_COPY_FN = None


def _retain_many(vals: list) -> list:
    """Pin state values for a deferred capture. jax arrays are immutable
    but NOT immortal: the executor donates state buffers into the next
    step's XLA call, which DELETES the original array — a bare reference
    read later by the capture thread would raise. ONE jitted device-side
    copy over the whole state (a single async dispatch; per-array
    jnp.copy calls would pay one dispatch each, which dominates small
    steps) decouples the snapshot's lifetime from the donation schedule.
    Outputs are fresh buffers by construction: XLA may only alias an
    input into an output when it is donated, and nothing here is."""
    global _COPY_FN
    import jax
    if _COPY_FN is None:
        import jax.numpy as jnp
        _COPY_FN = jax.jit(
            lambda xs: jax.tree_util.tree_map(jnp.copy, xs))
    return _COPY_FN(vals)


def snapshot_dir() -> str:
    """FLAGS_snapshot_dir -> PADDLE_SNAPSHOT_DIR (gang-shared, exported by
    the launch supervisor) -> a process-private temp dir."""
    from ..flags import flag
    d = str(flag("FLAGS_snapshot_dir") or "")
    d = d or os.environ.get("PADDLE_SNAPSHOT_DIR", "")
    return d or os.path.join(tempfile.gettempdir(),
                             f"paddle_tpu_snap_{os.getpid()}")


def _rank_world() -> Tuple[int, int]:
    return (int(os.environ.get("PADDLE_TRAINER_ID", "0") or 0),
            int(os.environ.get("PADDLE_TRAINERS_NUM", "1") or 1))


def _grace_s() -> float:
    try:
        return float(os.environ.get("PADDLE_LAUNCH_GRACE_S", "10"))
    except ValueError:
        return 10.0


def _portable_state(program, scope) -> Dict[str, np.ndarray]:
    """The snapshot payload: the portable-unsharded checkpoint collector
    plus the RNG state — a resumed replay must split the same keys or
    dropout/sampling steps diverge from the uninterrupted run."""
    from ..io import _portable_arrays
    arrays = _portable_arrays(program, scope)
    if scope.has(RNG_KEY):
        arrays[RNG_KEY] = rng_to_host(scope.find(RNG_KEY))
    return arrays


class Snapshot:
    """One complete in-memory snapshot: step tag + host arrays."""

    __slots__ = ("step", "arrays", "rank")

    def __init__(self, step: int, arrays: Dict[str, np.ndarray],
                 rank: int = 0):
        self.step = int(step)
        self.arrays = arrays
        self.rank = int(rank)

    def restore(self, scope) -> int:
        for n, arr in self.arrays.items():
            scope.set(n, rng_from_host(arr) if n == RNG_KEY else arr)
        return self.step


class SnapshotManager:
    """Double-buffered async snapshots for ONE trainer process.

        mgr = SnapshotManager(interval=4)
        ...
        mgr.maybe_capture(program, scope, step)    # per step, cheap
        mgr.flush("sigterm")                       # newest -> disk, atomic

    The executor drives `maybe_capture` automatically when
    FLAGS_snapshot_steps > 0; `install_sigterm_flush` arms the
    just-in-time flush for supervised gangs.
    """

    def __init__(self, interval: int = 0, root: Optional[str] = None,
                 rank: Optional[int] = None, world: Optional[int] = None):
        env_rank, env_world = _rank_world()
        self.interval = int(interval)
        self.root = root or snapshot_dir()
        self.rank = env_rank if rank is None else int(rank)
        self.world = env_world if world is None else int(world)
        self._buffers: list = [None, None]   # Snapshot double buffer
        self._newest = -1                    # index into _buffers, -1 = none
        self._peer: Optional[Snapshot] = None  # buddy's replicated payload
        self._lock = threading.Lock()
        self._job = None                     # (step, refs, program) pending
        self._job_ready = threading.Condition(self._lock)
        self._idle = threading.Event()
        self._idle.set()
        self._stop = False
        self._worker: Optional[threading.Thread] = None
        self._prev_handlers: dict = {}

    # -- capture -----------------------------------------------------------
    def _ensure_worker(self):
        if self._worker is None or not self._worker.is_alive():
            self._worker = threading.Thread(target=self._capture_loop,
                                            daemon=True,
                                            name="snapshot-capture")
            self._worker.start()

    def maybe_capture(self, program, scope, step: int,
                      sync: bool = False) -> bool:
        """Executor hook: on the snapshot cadence, grab references to the
        portable state names and hand them to the capture worker. Returns
        True when a capture was scheduled (or, with sync=True, completed).
        Never blocks on device readiness unless sync=True."""
        if self.interval <= 0 or step % self.interval != 0:
            return False
        if not self._idle.is_set():
            _metrics.inc("resilience.snapshot_skips")
            return False
        # Retain DEVICE COPIES, not bare references: the executor donates
        # state buffers into the next step's XLA call, so by the time the
        # capture thread reads a ref the original array may already be
        # deleted. One batched async copy dispatch (_retain_many) is the
        # only on-thread cost; the D2H transfer still happens off-thread.
        # Typed PRNG keys are pinned as their uint32 key-data words
        # (rng_from_host re-wraps them at restore).
        import jax
        from ..io import _persistable_names
        names = list(_persistable_names(program, scope))
        if scope.has(RNG_KEY):
            names.append(RNG_KEY)
        refs: dict = {}
        dev_names, dev_vals = [], []
        for n in names:
            v = scope.find(n)
            if isinstance(v, np.ndarray):
                refs[n] = v.copy()
                continue
            if hasattr(v, "dtype") and jax.dtypes.issubdtype(
                    v.dtype, jax.dtypes.prng_key):
                v = jax.random.key_data(v)
            dev_names.append(n)
            dev_vals.append(v)
        if dev_vals:
            refs.update(zip(dev_names, _retain_many(dev_vals)))
        with self._lock:
            self._job = (int(step), refs, program)
            self._idle.clear()
            self._job_ready.notify()
        self._ensure_worker()
        if sync:
            self.wait()
        return True

    def _capture_loop(self):
        while True:
            with self._lock:
                while self._job is None and not self._stop:
                    self._job_ready.wait(timeout=0.5)
                if self._stop:
                    return
                step, refs, program = self._job
                self._job = None
            try:
                self._capture(step, refs, program)
            finally:
                self._idle.set()

    def _capture(self, step: int, refs: dict, program):
        from ..parallel.zero import unbucket_state_for_save
        t0 = time.perf_counter()
        rng = refs.pop(RNG_KEY, None)
        arrays = {n: np.asarray(v) for n, v in refs.items()}
        arrays = unbucket_state_for_save(program, arrays)
        if rng is not None:
            arrays[RNG_KEY] = rng_to_host(rng)
        snap = Snapshot(step, arrays, rank=self.rank)
        with self._lock:
            standby = 1 - self._newest if self._newest >= 0 else 0
            self._buffers[standby] = snap
            self._newest = standby        # swap AFTER the copy completed
        dt_ms = (time.perf_counter() - t0) * 1000.0
        _metrics.inc("resilience.snapshots")
        _trace.instant("snapshot", args={"step": step,
                                         "ms": round(dt_ms, 3)},
                       cat="resilience")

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until no capture is in flight (tests / flush)."""
        return self._idle.wait(timeout)

    def latest(self) -> Optional[Snapshot]:
        with self._lock:
            return self._buffers[self._newest] if self._newest >= 0 else None

    def peer_payload(self) -> Optional[Snapshot]:
        with self._lock:
            return self._peer

    # -- peer replication --------------------------------------------------
    def replicate(self, gloo) -> Optional[int]:
        """Ship the newest snapshot to the ring buddy (rank+1 mod world)
        over the gloo transport; retain the buddy (rank-1 mod world)'s
        payload. One all-gather round; every rank must call it (it is a
        collective). Returns the step of the received peer payload, or
        None when the buddy had nothing yet."""
        self.wait()
        snap = self.latest()
        mine = (None if snap is None
                else (snap.step, {n: np.asarray(a)
                                  for n, a in snap.arrays.items()}))
        gathered = gloo.all_gather(mine)
        buddy = (self.rank - 1) % max(self.world, 1)
        payload = gathered[buddy] if buddy != self.rank else None
        with self._lock:
            if payload is not None:
                self._peer = Snapshot(payload[0], payload[1], rank=buddy)
        if payload is not None:
            return int(payload[0])
        return None

    # -- flush + SIGTERM ---------------------------------------------------
    def _own_dir(self, rank: Optional[int] = None) -> str:
        return os.path.join(self.root,
                            f"rank{self.rank if rank is None else rank}")

    def _peer_dir(self, origin_rank: int) -> str:
        return os.path.join(self.root, f"peer_of_rank{origin_rank}")

    def flush(self, reason: str = "manual") -> Optional[str]:
        """Write the newest complete snapshot (and the held peer payload)
        to disk through CheckpointManager — atomic publish, checksummed
        manifest, previous flush preserved on a torn write. Bounded by the
        launcher grace budget: host arrays only, no device sync beyond any
        capture already in flight."""
        self.wait(timeout=max(1.0, _grace_s() * 0.5))
        snap = self.latest()
        with self._lock:
            peer = self._peer
        path = None
        if snap is not None:
            mgr = CheckpointManager(self._own_dir(), max_keep=2)
            path = mgr.save(snap.step, arrays=snap.arrays,
                            meta={"kind": "snapshot", "reason": reason,
                                  "rank": self.rank})
        if peer is not None:
            mgr = CheckpointManager(self._peer_dir(peer.rank), max_keep=2)
            mgr.save(peer.step, arrays=peer.arrays,
                     meta={"kind": "peer_snapshot", "reason": reason,
                           "origin_rank": peer.rank,
                           "held_by_rank": self.rank})
        return path

    def install_sigterm_flush(self, exit_after: bool = True) -> None:
        """Arm just-in-time checkpointing: SIGTERM/SIGUSR1 flushes the
        newest snapshot (own + held peer payload) inside the launcher
        grace window, then chains the previous handler and (by default)
        exits 143 like a clean preemption. Main thread only; idempotent."""
        if threading.current_thread() is not threading.main_thread():
            return

        def _on_signal(signum, frame):
            try:
                self.flush(reason=f"signal_{signum}")
            except Exception:
                # a failed flush (disk full, injected fault) must not eat
                # the signal: the previous good flush is still published
                # (atomic rename), and the chain below still runs
                pass
            prev = self._prev_handlers.get(signum)
            if callable(prev):
                prev(signum, frame)
            if exit_after:
                raise SystemExit(128 + int(signum))

        for sig in (signal.SIGTERM, signal.SIGUSR1):
            try:
                prev = signal.signal(sig, _on_signal)
            except (ValueError, OSError):
                continue
            if sig not in self._prev_handlers:
                self._prev_handlers[sig] = prev

    def uninstall(self) -> None:
        for sig, prev in list(self._prev_handlers.items()):
            try:
                signal.signal(sig, prev)
            except (ValueError, OSError):
                pass
            self._prev_handlers.pop(sig, None)

    def close(self):
        with self._lock:
            self._stop = True
            self._job_ready.notify()
        self.uninstall()


# -- recovery ladder --------------------------------------------------------

def _load_rung(root_dir: str) -> Optional[Tuple[int, Dict[str, np.ndarray]]]:
    """Newest VALID flushed snapshot under `root_dir`, or None. Torn
    flushes fall back exactly like checkpoints (same manager)."""
    if not os.path.isdir(root_dir):
        return None
    mgr = CheckpointManager(root_dir, max_keep=2)
    step, payload = mgr.latest_valid()
    if step is None:
        return None
    return int(payload.get("step", step)), mgr.load_arrays(step)


def recover(scope, root: Optional[str] = None, rank: Optional[int] = None,
            ckpt_manager: Optional[CheckpointManager] = None,
            stamp: bool = True) -> Tuple[Optional[str], Optional[int]]:
    """The recovery ladder: peer snapshot -> local snapshot -> disk
    CheckpointManager. Restores the first rung that holds a complete
    state into `scope` and returns ("peer"|"local"|"disk", step), or
    (None, None) when every rung is empty (fresh start).

    The peer rung reads the payload a SURVIVING buddy flushed for this
    rank (`peer_of_rank<r>/`) — the rung that makes a replaced host's
    state recoverable with zero checkpoint-interval loss. `stamp=True`
    records the outcome in `<root>/recovery_rank<r>.json` so the gang
    supervisor prints the chosen rung in its log."""
    env_rank, _ = _rank_world()
    rank = env_rank if rank is None else int(rank)
    root = root or snapshot_dir()
    mgr_stub = SnapshotManager(root=root, rank=rank)
    rungs = [("peer", lambda: _load_rung(mgr_stub._peer_dir(rank))),
             ("local", lambda: _load_rung(mgr_stub._own_dir()))]
    chosen, step = None, None
    for name, load in rungs:
        got = load()
        if got is None:
            continue
        step, arrays = got
        Snapshot(step, arrays, rank=rank).restore(scope)
        chosen = name
        break
    if chosen is None and ckpt_manager is not None:
        restored = ckpt_manager.restore_latest(scope=scope)
        if restored is not None:
            chosen, step = "disk", int(restored)
    if stamp:
        _stamp_recovery(root, rank, chosen, step)
    return chosen, step


def _stamp_recovery(root: str, rank: int, rung: Optional[str],
                    step: Optional[int]) -> None:
    """Atomic rung record for the supervisor's gang log. Never raises."""
    try:
        os.makedirs(root, exist_ok=True)
        path = os.path.join(root, f"recovery_rank{rank}.json")
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"rank": rank, "rung": rung or "none",
                       "step": step, "pid": os.getpid(),
                       "wall_time": time.time()}, f)
        os.replace(tmp, path)
    except OSError:
        pass


def read_recovery_stamps(root: str, since: float = 0.0) -> list:
    """The supervisor side: rung records written after `since`, sorted by
    rank (distributed/launch.py prints them into the gang log)."""
    out = []
    try:
        names = os.listdir(root)
    except OSError:
        return out
    for name in names:
        if not (name.startswith("recovery_rank")
                and name.endswith(".json")):
            continue
        try:
            with open(os.path.join(root, name)) as f:
                rec = json.load(f)
            if float(rec.get("wall_time") or 0.0) >= since:
                out.append(rec)
        except (OSError, ValueError):
            continue
    return sorted(out, key=lambda r: int(r.get("rank", 0)))
