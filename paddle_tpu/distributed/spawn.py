"""paddle.distributed.spawn — multiprocessing entry for data-parallel
training functions.

Reference counterpart: python/paddle/distributed/spawn.py (spawns nprocs
worker processes, wires the PADDLE_* env contract, joins and re-raises the
first failure). TPU note: within one host all chips belong to ONE process
(single-controller jax), so nprocs>1 here means multi-host-style simulation
processes — each worker gets its own rank/endpoint env exactly like the
reference, and sharding tests use the virtual CPU mesh inside each worker.
No chip is assigned to a worker, so nprocs>1 is refused unless the workers'
environment pins JAX_PLATFORMS=cpu (launch.require_cpu_for_multiproc).
"""
from __future__ import annotations

import multiprocessing as mp
import os
import pickle
import socket
import traceback


def free_ports(n: int = 1):
    """Reserve n distinct free localhost ports (sockets held open until all
    are bound, so concurrent launches can't race each other to the same
    port)."""
    socks = []
    try:
        for _ in range(n):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def _worker(func, rank, nprocs, endpoints, env_extra, q, args):
    os.environ.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(nprocs),
        "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
        "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
        "TRAINING_ROLE": "TRAINER",
        **(env_extra or {}),
    })
    try:
        out = func(*args)
        q.put((rank, "ok", pickle.dumps(out)))
    except BaseException:
        q.put((rank, "error", traceback.format_exc()))
        raise


class SpawnContext:
    def __init__(self, procs, queue):
        self.processes = procs
        self._queue = queue
        self.results = {}

    def join(self, timeout=None):
        # drain the queue BEFORE joining: a child whose result exceeds the
        # pipe buffer can't exit until someone reads it (the classic
        # multiprocessing join/Queue deadlock)
        import queue as _q
        pending = len(self.processes)
        while pending:
            try:
                rank, status, payload = self._queue.get(
                    timeout=timeout or 600)
            except _q.Empty:
                break   # a worker died before reporting; exitcode check below
            pending -= 1
            if status == "error":
                raise RuntimeError(
                    f"spawned trainer {rank} failed:\n{payload}")
            self.results[rank] = pickle.loads(payload)
        for p in self.processes:
            p.join(timeout)
        for p in self.processes:
            if p.exitcode not in (0, None):
                raise RuntimeError(
                    f"spawned trainer pid={p.pid} exited {p.exitcode}")
        return True


def spawn(func, args=(), nprocs=1, join=True, daemon=False, **options):
    """Launch `func` in nprocs processes with the trainer env contract.
    Returns a SpawnContext (reference spawn.py return)."""
    from .launch import require_cpu_for_multiproc
    require_cpu_for_multiproc(
        nprocs, {**os.environ, **(options.get("env") or {})})
    ctx = mp.get_context(options.get("start_method", "spawn"))
    ports = free_ports(nprocs)
    endpoints = [f"127.0.0.1:{p}" for p in ports]
    q = ctx.Queue()
    procs = []
    for rank in range(nprocs):
        p = ctx.Process(target=_worker,
                        args=(func, rank, nprocs, endpoints,
                              options.get("env"), q, args),
                        daemon=daemon)
        p.start()
        procs.append(p)
    sctx = SpawnContext(procs, q)
    if join:
        sctx.join()
    return sctx
