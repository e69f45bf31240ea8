"""Lazy fetches: FetchHandle wraps a live device array until host access.

Reference counterpart: the fetch_op + FetchList drain in
paddle/fluid/framework/executor.cc (every run round-trips fetched values to
host LoDTensors). The TPU-native design inverts that default: a fetch is a
HANDLE onto the device buffer the step produced, and the D2H transfer (plus
the implied device sync — the value cannot leave before every queued
dispatch that feeds it) happens only when somebody actually reads it.
A training loop that logs loss every N steps therefore pays N-fold fewer
syncs; on dispatch-taxed links (docs/perf_notes.md "Round 5": ~350 ms
per-dispatch floor, ~72 MB/s D2H) the host simply never blocks on steps
nobody looks at.

Accounting: every materialization adds to the `executor.fetch_sync_count`
and `executor.host_blocked_ms` monitor stats — the same counters the sync
path's unconditional drain feeds — so `bench.py`'s pipelined-loop A/B and
`scripts/ci.py`'s host-stall budget check read one ledger for both modes.
"""
from __future__ import annotations

import time
from typing import Optional

import numpy as np

from .. import monitor
from ..observability import trace as _trace


def _record_sync(dt_s: float, n_values: int = 1):
    """One ledger for every host materialization (lazy or eager)."""
    monitor.stat_add("executor.fetch_sync_count", n_values)
    monitor.stat_add("executor.host_blocked_ms", dt_s * 1000.0)


class FetchHandle:
    """A fetch that has been DISPATCHED but not drained.

    Wraps the live device array an `Executor.run(..., sync=False)` /
    `run_steps(..., sync=False)` step produced. Shape/dtype are visible
    without blocking (jax arrays expose metadata eagerly); the value
    crosses to host — paying the device sync + D2H — only on `.numpy()`,
    `np.asarray(handle)`, `float(handle)`, or any other value access, and
    the result is cached so repeated reads pay once.

    `handle[idx]` stays lazy: it dispatches a device-side slice and
    returns a new handle, so `loss_handle[-1].numpy()` of a stacked
    run_steps fetch pulls ONE scalar instead of the [k]-vector.

    Tracing: a handle minted by the executor carries the FLOW id its
    dispatch opened (observability/trace.py); the first materialization
    records a `fetch.materialize` span and closes the flow — on whatever
    thread it happens — so the chrome trace draws the dispatch→drain arrow
    across threads.
    """

    __slots__ = ("_value", "_materialized", "name", "_flow")

    def __init__(self, value, name: Optional[str] = None,
                 flow=None):
        self._value = value
        self._materialized: Optional[np.ndarray] = None
        self.name = name
        # one-shot claim CELL shared by the parent and every lazy slice
        # (__getitem__ passes the same list): whichever handle in the
        # family materializes first pops it and closes the flow, so
        # `h[0].numpy(); h[-1].numpy()` leaves no dangling flow-start
        if flow is None or isinstance(flow, list):
            self._flow = flow
        else:
            self._flow = [flow]

    # ---- metadata (never blocks) ----------------------------------------
    @property
    def shape(self):
        return tuple(self._value.shape) if self._materialized is None \
            else self._materialized.shape

    @property
    def dtype(self):
        return (self._value if self._materialized is None
                else self._materialized).dtype

    @property
    def ndim(self):
        return len(self.shape)

    def is_materialized(self) -> bool:
        return self._materialized is not None

    @property
    def device_array(self):
        """The wrapped device array (un-drained; for re-feeding or
        device-side reductions). After materialization the host copy is
        authoritative; a slice of a materialized handle carries only the
        host copy (device_array is None there)."""
        return self._value

    # ---- materialization (blocks; counted) ------------------------------
    def numpy(self) -> np.ndarray:
        if self._materialized is None:
            with _trace.RecordEvent("fetch.materialize",
                                    args={"name": self.name}):
                t0 = time.perf_counter()
                self._materialized = np.asarray(self._value)
                _record_sync(time.perf_counter() - t0)
            if self._flow is not None:
                try:
                    fid = self._flow.pop()   # atomic claim under the GIL
                except IndexError:
                    fid = None               # a sibling already closed it
                if fid is not None:
                    _trace.flow_end("fetch", fid,
                                    args={"name": self.name})
                self._flow = None
        return self._materialized

    def __array__(self, dtype=None, copy=None):
        arr = self.numpy()
        if dtype is not None and arr.dtype != np.dtype(dtype):
            return arr.astype(dtype)
        if copy:
            return arr.copy()
        return arr

    def __float__(self):
        # numpy semantics exactly (size-1 converts, larger raises): the
        # async mode must never turn a sync-path TypeError into a silent
        # first-element read
        return float(self.numpy())

    def __int__(self):
        return int(self.numpy())

    def __bool__(self):
        return bool(self.numpy())

    def item(self):
        return self.numpy().item()

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of a scalar FetchHandle")
        return self.shape[0]

    def __getitem__(self, key):
        """Always returns a FetchHandle (type-stable regardless of
        whether the parent was already materialized): before
        materialization it is a lazy device-side slice, so indexing a
        [k]-stacked run_steps fetch does not drain the stack; after, it
        wraps the host slice (already-paid, never re-counted)."""
        if self._materialized is not None:
            # already paid: slice the host copy only — no device dispatch
            sub = FetchHandle(None, name=self.name)
            sub._materialized = self._materialized[key]
            return sub
        # SHARE the dispatch-flow claim with the slice: the documented
        # `stacked[-1].numpy()` pattern materializes the slice, but the
        # parent (or another slice) may drain first — whoever does closes
        # the arrow, exactly once
        return FetchHandle(self._value[key], name=self.name,
                           flow=self._flow)

    def __repr__(self):
        state = ("materialized" if self._materialized is not None
                 else "device")
        nm = f" {self.name!r}" if self.name else ""
        return (f"<FetchHandle{nm} shape={self.shape} "
                f"dtype={self.dtype} [{state}]>")
