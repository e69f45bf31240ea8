"""Parameter-server mode: sparse embedding tables on a host KV service.

Reference counterparts: the PS stack of §2.4/§2.8 —
operators/distributed/large_scale_kv.h (huge sparse tables),
parameter_prefetch.cc (pull rows by id before the step),
communicator.h:268 (async merge+send), listen_and_serv_op.cc (server loop),
heart_beat_monitor.cc (lost-worker detection), and the fleet PS runtime
(fleet/runtime/parameter_server_runtime.py).

TPU-native split (SURVEY §7): the DENSE math stays in the jitted XLA step;
only the sparse table lives host-side in the C++ KV service
(native/kvstore.cc). Per step the trainer:
  1. pulls the batch's unique rows over TCP,
  2. feeds them as a dense [uniq, dim] input to the XLA step,
  3. fetches that input's gradient and pushes it back (sync) or hands it to
     the client's merging flush thread (a_sync — geo/async SGD semantics).
"""
from __future__ import annotations

import ctypes
import os
from typing import Dict, List, Optional

import numpy as np

from ..framework.errors import (DeadlineExceededError, Unavailable,
                                UnavailableError)
from ..monitor import stat_add
from ..native import load_native
from ..resilience import RetryPolicy, fault_point


def _lib():
    lib = load_native("kvstore")
    if lib is None:
        raise RuntimeError("native kvstore failed to build (g++ required)")
    if not getattr(lib, "_kv_configured", False):
        lib.kvs_create.restype = ctypes.c_void_p
        lib.kvs_create.argtypes = [ctypes.c_int, ctypes.POINTER(ctypes.c_int),
                                   ctypes.POINTER(ctypes.c_float),
                                   ctypes.c_uint64,
                                   ctypes.POINTER(ctypes.c_int)]
        lib.kvs_start.restype = ctypes.c_int
        lib.kvs_start.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.kvs_stop.argtypes = [ctypes.c_void_p]
        lib.kvs_lost_workers.restype = ctypes.c_int
        lib.kvs_lost_workers.argtypes = [ctypes.c_void_p, ctypes.c_double,
                                         ctypes.POINTER(ctypes.c_int),
                                         ctypes.c_int]
        lib.kvs_destroy.argtypes = [ctypes.c_void_p]
        lib.kvc_connect.restype = ctypes.c_void_p
        lib.kvc_connect.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                    ctypes.c_int, ctypes.c_int]
        for name in ("kvc_pull", "kvc_push"):
            getattr(lib, name).restype = ctypes.c_int
        lib.kvc_pull.argtypes = [ctypes.c_void_p, ctypes.c_uint,
                                 ctypes.POINTER(ctypes.c_longlong),
                                 ctypes.c_longlong,
                                 ctypes.POINTER(ctypes.c_float), ctypes.c_uint]
        lib.kvc_push.argtypes = [ctypes.c_void_p, ctypes.c_uint,
                                 ctypes.POINTER(ctypes.c_longlong),
                                 ctypes.c_longlong,
                                 ctypes.POINTER(ctypes.c_float),
                                 ctypes.c_uint, ctypes.c_float]
        lib.kvc_push_async.argtypes = lib.kvc_push.argtypes
        lib.kvc_push_delta.restype = ctypes.c_int
        lib.kvc_push_delta.argtypes = [ctypes.c_void_p, ctypes.c_uint,
                                       ctypes.POINTER(ctypes.c_longlong),
                                       ctypes.c_longlong,
                                       ctypes.POINTER(ctypes.c_float),
                                       ctypes.c_uint]
        lib.kvc_flush.restype = ctypes.c_int
        lib.kvc_flush.argtypes = [ctypes.c_void_p]
        lib.kvc_ping.restype = ctypes.c_int
        lib.kvc_ping.argtypes = [ctypes.c_void_p]
        lib.kvc_ping_deadline.restype = ctypes.c_int
        lib.kvc_ping_deadline.argtypes = [ctypes.c_void_p, ctypes.c_double]
        lib.kvc_reconnect.restype = ctypes.c_int
        lib.kvc_reconnect.argtypes = [ctypes.c_void_p]
        lib.kvc_set_io_timeout.restype = None
        lib.kvc_set_io_timeout.argtypes = [ctypes.c_void_p, ctypes.c_double]
        lib.kvc_table_size.restype = ctypes.c_longlong
        lib.kvc_table_size.argtypes = [ctypes.c_void_p, ctypes.c_uint]
        lib.kvc_save.restype = ctypes.c_int
        lib.kvc_save.argtypes = [ctypes.c_void_p, ctypes.c_uint,
                                 ctypes.c_char_p]
        lib.kvc_load.restype = ctypes.c_int
        lib.kvc_load.argtypes = [ctypes.c_void_p, ctypes.c_uint,
                                 ctypes.c_char_p]
        lib.kvc_close.argtypes = [ctypes.c_void_p]
        lib._kv_configured = True
    return lib


_OPT_CODES = {"sgd": 0, "adagrad": 1, "adam": 2}


class SparseTableConfig:
    def __init__(self, name: str, dim: int, init_scale: float = 0.01,
                 optimizer: str = "sgd"):
        """`optimizer` picks the SERVER-side update rule (the reference's
        pservers run arbitrary optimizer blocks, listen_and_serv_op.cc:127 /
        lookup_sparse_table_fuse_adam_op.cc): sgd | adagrad | adam, with
        per-row moment states held in the C++ table."""
        self.name = name
        self.dim = int(dim)
        self.init_scale = float(init_scale)
        assert optimizer in _OPT_CODES, f"unknown server optimizer {optimizer}"
        self.optimizer = optimizer


class KVServer:
    """The pserver process core (reference ListenAndServOp event loop)."""

    def __init__(self, tables: List[SparseTableConfig], seed: int = 0):
        self._lib = _lib()
        self.tables = list(tables)
        dims = (ctypes.c_int * len(tables))(*[t.dim for t in tables])
        scales = (ctypes.c_float * len(tables))(
            *[t.init_scale for t in tables])
        opts = (ctypes.c_int * len(tables))(
            *[_OPT_CODES[getattr(t, "optimizer", "sgd")] for t in tables])
        self._h = self._lib.kvs_create(len(tables), dims, scales, seed, opts)
        self.port = None

    def start(self, port: int = 0) -> int:
        self.port = int(self._lib.kvs_start(self._h, port))
        assert self.port > 0, "kv server failed to bind"
        return self.port

    def lost_workers(self, timeout_s: float = 60.0) -> List[int]:
        out = (ctypes.c_int * 1024)()
        n = self._lib.kvs_lost_workers(self._h, timeout_s, out, 1024)
        return list(out[:n])

    def stop(self):
        if self._h is not None:
            self._lib.kvs_stop(self._h)

    def __del__(self):
        try:
            if getattr(self, "_h", None) is not None:
                self._lib.kvs_stop(self._h)
                self._lib.kvs_destroy(self._h)
                self._h = None
        except Exception:
            pass


class KVClient:
    """Trainer-side client (reference Communicator + RPCClient).

    Resilience contract (resilience/, docs/resilience.md): every RPC method
    passes a fault_point ("kv.pull"/"kv.push"/"kv.flush"/"kv.ping") and runs
    under one RetryPolicy — transient failures back off and retry; an
    exhausted budget raises the typed DeadlineExceededError (an IOError
    subclass, so legacy call sites still catch it) instead of hanging.
    Retried pushes are at-least-once against a REAL half-applied network
    failure (same as the reference's async communicator, whose merged
    resends carry no dedup either); injected faults fire before any byte
    hits the wire, so chaos-run retries replay identical arithmetic.

    Every recv/send on the connection carries a persistent socket deadline
    (`io_timeout_s`, default FLAGS_rpc_deadline_ms) so a hung-but-connected
    server fails the op within the deadline instead of parking the trainer
    in recv() forever. A failed op leaves the length-prefixed stream
    desynced, so the connection is marked dead and the next attempt
    RECONNECTS (fresh socket, clean stream; reference brpc reconnect
    loops) before re-issuing the request.
    """

    def __init__(self, host: str, port: int, worker_id: int = 0,
                 a_sync: bool = False, flush_ms: int = 50,
                 retry: Optional[RetryPolicy] = None,
                 io_timeout_s: Optional[float] = None):
        self._lib = _lib()
        self.a_sync = a_sync
        # Default policy: attempt-bounded, NOT wall-clock-bounded. Each
        # attempt is already capped by the per-op socket deadline
        # (FLAGS_rpc_deadline_ms); reusing that same flag as the policy
        # deadline would let ONE hung RPC spend the whole budget and skip
        # the reconnect-and-retry path entirely. Worker_id is folded into
        # the jitter seed so N trainers retrying the same outage don't all
        # back off on one identical schedule (thundering herd); jitter
        # shifts timing only, never arithmetic.
        if retry is None:
            from ..flags import flag
            retry = RetryPolicy(deadline_s=None,
                                seed=int(flag("FLAGS_fault_seed"))
                                + int(worker_id) * 1000003)
        self._retry = retry
        self._host, self._port = host, int(port)
        self._worker_id = int(worker_id)
        self._flush_ms = int(flush_ms) if a_sync else 0
        if io_timeout_s is None:
            from ..flags import flag
            io_timeout_s = flag("FLAGS_rpc_deadline_ms") / 1000.0
        self._io_timeout_s = float(io_timeout_s)
        self._dead = False
        self._h = self._lib.kvc_connect(host.encode(), self._port,
                                        self._worker_id, self._flush_ms)
        if not self._h:
            raise ConnectionError(f"cannot reach pserver {host}:{port}")
        if self._io_timeout_s > 0:
            self._lib.kvc_set_io_timeout(
                self._h, ctypes.c_double(self._io_timeout_s))

    def _mark_dead(self):
        self._dead = True

    def _ensure_connected(self):
        """Reconnect after a failed op: the failure left the request/
        response stream desynced, so retrying on the old socket could read
        a stale reply as its own. The native client object survives the
        re-dial — crucially including merged-but-unsent async gradients a
        failed flush re-buffered — only the socket is replaced. Raises
        Unavailable (retryable) when the server is still unreachable."""
        if not self._h:
            raise Unavailable("pserver client %s:%d is closed",
                              self._host, self._port)
        if not self._dead:
            return
        if self._lib.kvc_reconnect(self._h) != 0:
            raise Unavailable("reconnect to pserver %s:%d failed",
                              self._host, self._port)
        self._dead = False

    def pull(self, table: int, keys: np.ndarray, dim: int) -> np.ndarray:
        keys = np.ascontiguousarray(keys, np.int64)

        def op():
            fault_point("kv.pull")
            self._ensure_connected()
            out = np.empty((len(keys), dim), np.float32)
            rc = self._lib.kvc_pull(
                self._h, table,
                keys.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
                len(keys),
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), dim)
            if rc != 0:
                self._mark_dead()
                raise Unavailable("kv pull failed (table %d, %d keys)",
                                  table, len(keys))
            return out

        return self._retry.call(op, site="kv.pull")

    def push(self, table: int, keys: np.ndarray, grads: np.ndarray,
             lr: float):
        keys = np.ascontiguousarray(keys, np.int64)
        grads = np.ascontiguousarray(grads, np.float32)

        def op():
            fault_point("kv.push")
            self._ensure_connected()
            fn = (self._lib.kvc_push_async if self.a_sync
                  else self._lib.kvc_push)
            rc = fn(self._h, table,
                    keys.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
                    len(keys),
                    grads.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                    grads.shape[1], float(lr))
            if not self.a_sync and rc != 0:
                self._mark_dead()
                raise Unavailable("kv push failed (table %d, %d keys)",
                                  table, len(keys))

        self._retry.call(op, site="kv.push")

    def push_delta(self, table: int, keys: np.ndarray, deltas: np.ndarray):
        """Geo-SGD: server applies w += delta (no lr)."""
        keys = np.ascontiguousarray(keys, np.int64)
        deltas = np.ascontiguousarray(deltas, np.float32)

        def op():
            fault_point("kv.push")
            self._ensure_connected()
            rc = self._lib.kvc_push_delta(
                self._h, table,
                keys.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
                len(keys),
                deltas.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                deltas.shape[1])
            if rc != 0:
                self._mark_dead()
                raise Unavailable("kv push_delta failed (table %d)", table)

        self._retry.call(op, site="kv.push")

    def flush(self):
        def op():
            fault_point("kv.flush")
            self._ensure_connected()
            if self._lib.kvc_flush(self._h) != 0:
                # the native side re-buffered the unsent gradients, so the
                # retried flush (post-reconnect) resends them
                self._mark_dead()
                raise Unavailable("kv flush failed")

        self._retry.call(op, site="kv.flush")

    def ping(self, timeout_s: Optional[float] = None) -> bool:
        """Heartbeat with an explicit deadline (default
        FLAGS_rpc_deadline_ms): a dead-but-connected endpoint answers False
        within the deadline instead of blocking recv() forever — the
        round-5 'dead relay ⇒ every dial hangs' class of bug. A timed-out
        ping poisons the connection (native side shuts the socket down), so
        later ops fail fast rather than desync."""
        if timeout_s is None:
            from ..flags import flag
            timeout_s = flag("FLAGS_rpc_deadline_ms") / 1000.0

        def op():
            fault_point("kv.ping")
            self._ensure_connected()
            ok = self._lib.kvc_ping_deadline(
                self._h, ctypes.c_double(float(timeout_s))) == 0
            if not ok:          # native side shut the socket down already;
                self._mark_dead()  # the next op reconnects first
            return ok

        try:
            return self._retry.call(op, site="kv.ping")
        except DeadlineExceededError:
            return False

    # table_size/save/load must also reconnect first: after an exhausted
    # retry budget the handle is None, and handing that to ctypes would
    # nullptr-deref in the native client instead of raising.
    def table_size(self, table: int) -> int:
        self._ensure_connected()
        return int(self._lib.kvc_table_size(self._h, table))

    def save(self, table: int, path: str):
        self._ensure_connected()
        if self._lib.kvc_save(self._h, table, path.encode()) != 0:
            self._mark_dead()
            raise Unavailable("kv save failed (table %d -> %s)", table, path)

    def load(self, table: int, path: str):
        self._ensure_connected()
        if self._lib.kvc_load(self._h, table, path.encode()) != 0:
            self._mark_dead()
            raise Unavailable("kv load failed (table %d <- %s)", table, path)

    def close(self):
        if self._h:
            self._lib.kvc_close(self._h)
            self._h = None


class HotRowCache:
    """Client-side hot-row cache tier — the box_ps/pslib cache re-imagining
    (reference box_wrapper caches hot embedding rows in device memory in
    front of the PS core; here: an LRU of host rows in front of the TCP
    pulls, the part of that design that is not closed-source).

    Correctness contract: a push to a key INVALIDATES it (server-side
    optimizers make local replay impossible to do honestly), and every
    entry expires after `max_stale_pulls` pull calls so other workers'
    pushes are picked up within a bounded staleness window — the standard
    async-PS staleness semantics. With one worker the cache is therefore
    EXACT (tests assert parity)."""

    def __init__(self, capacity_rows: int = 100_000,
                 max_stale_pulls: int = 16):
        from collections import OrderedDict
        self.capacity = int(capacity_rows)
        self.max_stale = int(max_stale_pulls)
        self._rows: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._tick = 0
        self.hits = 0
        self.misses = 0

    def start_pull(self):
        self._tick += 1

    def get(self, table: int, key: int):
        ent = self._rows.get((table, key))
        if ent is None:
            self.misses += 1
            return None
        row, birth = ent
        if self._tick - birth > self.max_stale:
            # expired: report a miss but KEEP the entry — it is the
            # degraded-mode fallback peek() serves when the re-pull finds
            # the server unreachable; LRU capacity still bounds memory
            self.misses += 1
            return None
        self._rows.move_to_end((table, key))
        self.hits += 1
        return row

    def peek(self, table: int, key: int):
        """Raw entry ignoring the staleness window — the degraded-mode read
        used when the server is unreachable within deadline (stale rows beat
        a dead run; staleness is counted via resilience.stale_served)."""
        ent = self._rows.get((table, key))
        return ent[0] if ent is not None else None

    def put(self, table: int, key: int, row) -> None:
        self._rows[(table, key)] = (row, self._tick)
        self._rows.move_to_end((table, key))
        while len(self._rows) > self.capacity:
            self._rows.popitem(last=False)

    def invalidate(self, table: int, keys) -> None:
        for k in np.asarray(keys).reshape(-1):
            self._rows.pop((table, int(k)), None)

    def clear(self) -> None:
        self._rows.clear()

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0


class ShardedKVClient:
    """Key-sharded client over multiple pservers (reference ps_dispatcher.py
    round-robin param placement; here rows shard by key hash, the
    large-scale-KV convention). Exposes the same pull/push surface as
    KVClient so hooks are agnostic. `cache_rows` > 0 puts a HotRowCache
    tier in front of pulls (PADDLE_PS_CACHE_ROWS env default)."""

    def __init__(self, endpoints: List[str], worker_id: int = 0,
                 a_sync: bool = False, cache_rows: int = None,
                 cache_max_stale: int = 16,
                 retry: Optional[RetryPolicy] = None):
        assert endpoints, "ShardedKVClient needs at least one endpoint"
        self.clients = []
        for ep in endpoints:
            host, port = ep.rsplit(":", 1)
            self.clients.append(KVClient(host, int(port), worker_id,
                                         a_sync=a_sync, retry=retry))
        self.a_sync = a_sync
        if cache_rows is None:
            cache_rows = int(os.environ.get("PADDLE_PS_CACHE_ROWS", "0"))
        # a_sync buffers pushes client-side (~50ms flush): a post-push pull
        # would re-cache the PRE-push server row and pin the worker's own
        # gradient invisible for max_stale pulls — read-your-writes breaks.
        # The cache tier is therefore a sync-mode feature.
        self.cache = (HotRowCache(cache_rows, cache_max_stale)
                      if cache_rows > 0 and not a_sync else None)

    def _shard(self, keys: np.ndarray):
        return (keys % len(self.clients)).astype(np.int64)

    def _pull_remote(self, table: int, keys: np.ndarray,
                     dim: int) -> np.ndarray:
        if len(self.clients) == 1:
            return self.clients[0].pull(table, keys, dim)
        out = np.empty((len(keys), dim), np.float32)
        shard = self._shard(keys)
        for s, c in enumerate(self.clients):
            m = shard == s
            if m.any():
                out[m] = c.pull(table, keys[m], dim)
        return out

    def pull(self, table: int, keys: np.ndarray, dim: int) -> np.ndarray:
        keys = np.ascontiguousarray(keys, np.int64)
        if self.cache is None:
            return self._pull_remote(table, keys, dim)
        self.cache.start_pull()
        out = np.empty((len(keys), dim), np.float32)
        miss = []
        for i, k in enumerate(keys):
            row = self.cache.get(table, int(k))
            if row is None:
                miss.append(i)
            else:
                out[i] = row
        if miss:
            try:
                rows = self._pull_remote(table, keys[miss], dim)
            except (UnavailableError, OSError) as e:
                # degraded mode: server unreachable within the retry budget —
                # serve expired-but-cached rows rather than kill the step
                # (standard async-PS staleness, just a wider window; counted
                # so operators see it happening)
                return self._stale_rows(table, keys, miss, out, e)
            for j, i in enumerate(miss):
                out[i] = rows[j]
                self.cache.put(table, int(keys[i]), rows[j].copy())
        return out

    def _stale_rows(self, table, keys, miss, out, err):
        for i in miss:
            row = self.cache.peek(table, int(keys[i]))
            if row is None:   # never seen this key: nothing to degrade to
                raise err
            out[i] = row
        stat_add("resilience.stale_served", len(miss))
        return out

    def push(self, table: int, keys: np.ndarray, grads: np.ndarray,
             lr: float):
        keys = np.ascontiguousarray(keys, np.int64)
        if self.cache is not None:
            self.cache.invalidate(table, keys)
        if len(self.clients) == 1:
            return self.clients[0].push(table, keys, grads, lr)
        shard = self._shard(keys)
        for s, c in enumerate(self.clients):
            m = shard == s
            if m.any():
                c.push(table, keys[m], np.ascontiguousarray(grads[m]), lr)

    def push_delta(self, table: int, keys: np.ndarray, deltas: np.ndarray):
        keys = np.ascontiguousarray(keys, np.int64)
        if self.cache is not None:
            self.cache.invalidate(table, keys)
        if len(self.clients) == 1:
            return self.clients[0].push_delta(table, keys, deltas)
        shard = self._shard(keys)
        for s, c in enumerate(self.clients):
            m = shard == s
            if m.any():
                c.push_delta(table, keys[m], np.ascontiguousarray(deltas[m]))

    def flush(self):
        for c in self.clients:
            c.flush()

    def ping(self, timeout_s: Optional[float] = None):
        return all(c.ping(timeout_s=timeout_s) for c in self.clients)

    def table_size(self, table: int) -> int:
        return sum(c.table_size(table) for c in self.clients)

    def save(self, table: int, path: str) -> List[str]:
        """Checkpoint `table` server-side; sharded deployments write one
        `<path>.shard<i>` per endpoint. Returns the written paths (the
        CheckpointManager puts each in the manifest)."""
        if len(self.clients) == 1:
            self.clients[0].save(table, path)
            return [path]
        paths = []
        for i, c in enumerate(self.clients):
            p = f"{path}.shard{i}"
            c.save(table, p)
            paths.append(p)
        return paths

    def load(self, table: int, path: str):
        """Restore `table` from a save() of the same endpoint count. Cached
        rows are dropped: they describe the pre-restore table."""
        if self.cache is not None:
            self.cache.clear()
        if len(self.clients) == 1:
            return self.clients[0].load(table, path)
        for i, c in enumerate(self.clients):
            c.load(table, f"{path}.shard{i}")

    def close(self):
        for c in self.clients:
            c.close()


# ---------------------------------------------------------------------------
# program-level integration: distributed embedding pulls/pushes around the
# jitted step (reference parameter_prefetch.cc + distributed_lookup_table op)
# ---------------------------------------------------------------------------

class _PsHook:
    """Pre/post hook pair the Executor fires around each run.

    Two modes (reference communicator.h):
    - sync/async (geo_k == 0): pull fresh rows each step, push grads after
      (the server applies its configured optimizer rule).
    - Geo-SGD (geo_k > 0, communicator.h:413 GeoCommunicator): the trainer
      keeps LOCAL row copies and trains them with local SGD; every k-th
      step it pushes param DELTAS (local - base) and re-pulls, so multiple
      trainers' deltas merge additively on the server.
    """

    def __init__(self, table_idx: int, ids_name: str, pulled_name: str,
                 grad_name: str, dim: int, lr: float):
        self.table_idx = table_idx
        self.ids_name = ids_name
        self.pulled_name = pulled_name
        self.grad_name = grad_name
        self.dim = dim
        self.lr = lr
        self.client: Optional[KVClient] = None
        self._last_uniq = None
        # geo state — bounded to the ids touched since the last sync (the
        # reference GeoCommunicator sends only recently-touched ids too)
        self.geo_k = 0
        self._step = 0
        self._local: dict = {}     # id -> local row (np)
        self._base: dict = {}      # id -> row at last sync
        self._touched: set = set()

    def _geo_rows(self, uniq: np.ndarray) -> np.ndarray:
        missing = np.asarray([k for k in uniq if k not in self._local],
                             np.int64)
        if len(missing):
            pulled = self.client.pull(self.table_idx, missing, self.dim)
            for k, row in zip(missing, pulled):
                self._local[k] = row.copy()
                self._base[k] = row.copy()
        return np.stack([self._local[k] for k in uniq])

    def pre(self, feed: dict) -> dict:
        ids = np.asarray(feed[self.ids_name]).reshape(-1)
        uniq, inverse = np.unique(ids, return_inverse=True)
        if self.geo_k > 0:
            rows = self._geo_rows(uniq)
        else:
            rows = self.client.pull(self.table_idx, uniq, self.dim)
        # pad the row count to a power-of-two bucket: the jitted step
        # specializes on feed shapes, so raw unique counts would recompile
        # every batch (same trick as the reference's fixed-capacity pull
        # buffers in parameter_prefetch)
        bucket = max(8, 1 << int(np.ceil(np.log2(max(len(uniq), 1)))))
        padded = np.zeros((bucket, self.dim), np.float32)
        padded[:len(uniq)] = rows
        self._last_uniq = uniq
        batch_shape = np.asarray(feed[self.ids_name]).shape
        return {self.pulled_name: padded,
                self.ids_name + "@inverse":
                    inverse.reshape(batch_shape).astype(np.int32)}

    def pre_multi(self, feed: dict) -> dict:
        """k-step window pull (reference communicator.h async mode +
        DistMultiTrainer thread pools, trainer.h:121): ONE KV round-trip
        covers the union of the window's ids, the device runs k steps in
        one dispatch (Executor.run_steps), and post_multi pushes the summed
        row grads in one round-trip. Rows are frozen within the window —
        the declared a_sync staleness (k dispatch costs and 2k-2 RPCs are
        saved per window; see docs/perf_notes.md roofline). The ids feed is
        either [k, ...] per-step slices or run_steps' broadcast form (one
        batch replicated each step); both reshape consistently below."""
        ids = np.asarray(feed[self.ids_name])
        uniq, inverse = np.unique(ids.reshape(-1), return_inverse=True)
        rows = self.client.pull(self.table_idx, uniq, self.dim)
        bucket = max(8, 1 << int(np.ceil(np.log2(max(len(uniq), 1)))))
        padded = np.zeros((bucket, self.dim), np.float32)
        padded[:len(uniq)] = rows
        self._last_uniq = uniq
        # pulled rows broadcast to every step (per-step rank, no [k] axis);
        # inverse indices keep the [k, ...] per-step slicing
        return {self.pulled_name: padded,
                self.ids_name + "@inverse":
                    inverse.reshape(ids.shape).astype(np.int32)}

    def post_multi(self, fetched: dict):
        """Push the window's summed grads: with rows frozen intra-window,
        sum-of-step-grads applied once equals the k sequential updates."""
        g = fetched.get(self.grad_name)
        if g is None or self._last_uniq is None:
            return
        g = np.asarray(g)                       # [k, bucket, dim]
        g = g.sum(axis=0)[:len(self._last_uniq)]
        self.client.push(self.table_idx, self._last_uniq, g, self.lr)

    def post(self, fetched: dict):
        g = fetched.get(self.grad_name)
        if g is None or self._last_uniq is None:
            return
        g = np.asarray(g)[:len(self._last_uniq)]
        if self.geo_k <= 0:
            self.client.push(self.table_idx, self._last_uniq, g, self.lr)
            return
        # geo: local SGD step on the cached rows
        for k, grow in zip(self._last_uniq, g):
            self._local[k] -= self.lr * grow
            self._touched.add(int(k))
        self._step += 1
        if self._step % self.geo_k == 0:
            self._geo_sync()

    def _geo_sync(self):
        """Push deltas for ids touched since the last sync, re-pull them,
        then evict everything else — bounding trainer memory and per-sync
        traffic to the recent working set (untouched cached rows are stale
        against other trainers anyway; next use re-pulls them)."""
        if not self._touched:
            self._local.clear()
            self._base.clear()
            return
        keys = np.fromiter(self._touched, np.int64, count=len(self._touched))
        delta = np.stack([self._local[k] - self._base[k] for k in keys])
        self.client.push_delta(self.table_idx, keys, delta)
        fresh = self.client.pull(self.table_idx, keys, self.dim)
        self._local = {int(k): row.copy() for k, row in zip(keys, fresh)}
        self._base = {int(k): row.copy() for k, row in zip(keys, fresh)}
        self._touched.clear()


def distributed_embedding(ids, table_name: str, dim: int,
                          lr: float = 0.1):
    """Sparse embedding served by the KV service. Builds:
    pulled[uniq, dim] (fed by the pre-hook) gathered by ids@inverse — the
    gather runs on-device, the unique/pull on host (reference
    distributed_lookup_table_op.cc semantics)."""
    from ..layer_helper import LayerHelper
    from ..framework.program import default_main_program
    program = default_main_program()
    helper = LayerHelper("distributed_embedding")
    block = program.global_block()

    pulled = block.create_var(name=f"{table_name}@pulled", shape=(-1, dim),
                              dtype="float32", is_data=True)
    pulled.stop_gradient = False
    inverse = block.create_var(name=ids.name + "@inverse",
                               shape=tuple(ids.shape), dtype="int32",
                               is_data=True)
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op("gather", inputs={"X": [pulled], "Index": [inverse]},
                     outputs={"Out": [out]})
    hooks = getattr(program, "_ps_hooks", None)
    if hooks is None:
        hooks = program._ps_hooks = []
    hooks.append(_PsHook(len(hooks), ids.name, pulled.name,
                         pulled.name + "@GRAD", dim, lr))
    program._ps_tables = getattr(program, "_ps_tables", [])
    program._ps_tables.append(SparseTableConfig(table_name, dim))
    return out
