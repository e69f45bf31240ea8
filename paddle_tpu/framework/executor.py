"""Executor: lowers a Program block to ONE jitted XLA computation.

Reference counterpart: paddle/fluid/framework/executor.cc (op-by-op interpreter,
hot loop at :474-482) + python/paddle/fluid/executor.py:916. The TPU-native
design deliberately differs: instead of interpreting ops one by one (a host
round-trip per op), the whole block is traced once into a single JAX function
— every op's lowering inlines into one jaxpr — and XLA compiles/fuses it.
Persistable state (params, optimizer moments, BN stats) is threaded through the
function functionally and donated, so updates are in-place in HBM.

Compile cache key = (program identity+version, feed shapes/dtypes, fetch names),
mirroring the reference's ExecutorPrepareContext caching.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import itertools
import time
from typing import Dict, List, NamedTuple, Optional, Sequence

import jax
import numpy as np

from .program import (OpRole, Program, Variable, default_main_program,
                      default_startup_program)
from .scope import Scope, global_scope
from .. import monitor
from ..observability import flight as _flight
from ..observability import metrics as _metrics
from ..observability import scopes as _scopes
from ..observability import trace as _trace
from ..ops import registry

# flight-recorder owner ids: stable per Executor instance (id() can be
# reused after GC), assigned lazily by _step_window
_flight_owner_ids = itertools.count(1)
# until the first `executor.step` root of a main program has closed: the
# gauge `startup.time_to_first_step_s` is set there, once a process
_first_step_unread = True


class _CompiledBlock:
    """A block lowered + jitted for one (feed-spec, fetch-list) signature."""

    def __init__(self, program: Program, block_idx: int,
                 feed_names: Sequence[str], fetch_names: Sequence[str],
                 state_names: Sequence[str], donate: bool = True,
                 feed_shapes: Optional[dict] = None,
                 state_shapes: Optional[dict] = None, multi_k: int = 0,
                 feed_dtypes: Optional[dict] = None,
                 state_dtypes: Optional[dict] = None):
        self.program = program
        self.block = program.blocks[block_idx]
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        self.state_names = list(state_names)
        self.written_state: List[str] = self._written_persistables()
        written = set(self.written_state)
        # donate only buffers that get overwritten (params/opt state); purely
        # read state stays un-donated so XLA keeps it resident. In the
        # PER-STEP path, written buffers BELOW the FLAGS_min_donate_bytes
        # floor are also left un-donated: donating a tiny buffer (an Adam
        # beta-pow, a LayerNorm scale) saves a few bytes of HBM but forces
        # in-place aliasing, and whenever XLA schedules the update before a
        # remaining read of the old value it must insert a value-preserving
        # copy op — at BERT scale those tiny-state copies dominated the
        # compiled step's copy census (docs/perf_notes.md "Copy census").
        # Un-donated writes just come back as fresh buffers the Scope
        # adopts. The k-step scan path donates EVERYTHING written: the scan
        # carry's buffers alias in place regardless (so the floor cannot
        # remove in-body copies there), while an un-donated input would add
        # an entry copy INTO the carry.
        from ..flags import flag
        floor = 0 if multi_k else int(flag("FLAGS_min_donate_bytes") or 0)

        def _donate_ok(n):
            if n not in written:
                return False
            if floor <= 0:
                return True
            shp = (state_shapes or {}).get(n)
            if shp is None:
                v = self.block.find_var_recursive(n)
                shp = tuple(v.shape) if v is not None else ()
            return _buffer_nbytes(self.block, n, shp) >= floor

        self.mut_names = [n for n in self.state_names if _donate_ok(n)]
        mut_set = set(self.mut_names)
        self.ro_names = [n for n in self.state_names if n not in mut_set]
        micro_k = getattr(program, "_microbatch_k", 0)
        if multi_k:      # any k >= 1: feeds always carry the leading [k] axis
            runner = functools.partial(_run_block_multistep, multi_k)
        elif micro_k and micro_k > 1:
            runner = functools.partial(_run_block_microbatched, micro_k)
        else:
            runner = _run_block
        fn = functools.partial(runner, self.block, self.feed_names,
                               self.fetch_names, self.mut_names, self.ro_names,
                               self.written_state)
        jit_kw = {}
        self.manual_dp = False
        # name -> NamedSharding the jitted step declares for its state
        # inputs (empty without a mesh); see place_state
        self.state_shardings = {}
        dist = getattr(program, "_dist_config", None)
        if dist is not None:
            # SPMD: shard feeds over the data axes, params per TP rules; XLA
            # GSPMD inserts every collective (the grad allreduce included)
            mesh = dist.resolve_mesh()
            self.mesh = mesh

            # Bucketed-collectives path (parallel/zero.py): on a dp-pure
            # mesh a bucketed program runs the whole step under shard_map,
            # so its gradient sync is the few grouped __bucket_sync__ /
            # __zero_update__ collectives instead of one GSPMD all-reduce
            # per parameter. Any structural obstacle (mixed mesh,
            # cross-batch ops, indivisible batch, plan/trace failure) falls
            # back to the GSPMD lowering below.
            if getattr(program, "_grad_buckets", None) is not None \
                    and not (micro_k and micro_k > 1):
                from ..parallel import zero as zero_mod
                feed_meta = {
                    n: (tuple((feed_shapes or {}).get(n, ())),
                        (feed_dtypes or {}).get(n, np.float32))
                    for n in self.feed_names}
                state_meta = {
                    n: (tuple((state_shapes or {}).get(n, ())),
                        (state_dtypes or {}).get(n, np.float32))
                    for n in self.state_names}
                try:
                    plan = zero_mod.plan_manual_dp(
                        program, dist, mesh, self.block, fn, feed_meta,
                        state_meta, self.fetch_names, self.written_state,
                        multi_k)
                except Exception:
                    # plan/trace failure: the structural causes are counted
                    # inside plan_manual_dp itself (per-cause breakdown
                    # under executor.zero_manual_fallbacks.<cause>)
                    zero_mod.count_fallback("plan_failure")
                    plan = None
                if plan is not None:
                    self.jitted = zero_mod.build_manual_jit(
                        plan, fn, self.mut_names, self.ro_names,
                        donate=donate)
                    self.manual_dp = True
                    from jax.sharding import NamedSharding
                    self.state_shardings = {
                        n: NamedSharding(mesh, plan.state_specs[n])
                        for n in self.state_names}
                    return

            zero_specs = getattr(program, "_zero_state_specs", None) or {}

            def state_shard(names):
                from jax.sharding import NamedSharding, PartitionSpec
                out = {}
                for n in names:
                    shp = (state_shapes or {}).get(n)
                    if shp is None:
                        v = self.block.find_var_recursive(n)
                        shp = tuple(v.shape) if v is not None else None
                    if n in zero_specs:
                        # flat ZeRO bucket state (moments/grad/param):
                        # dp-sharded storage even on the GSPMD path (mixed
                        # meshes keep the ~dp x memory saving; GSPMD
                        # inserts the collectives from the spec),
                        # replicated when the padding does not divide the
                        # dp width (one shared divisibility rule)
                        from ..parallel.zero import flat_state_partition
                        out[n] = NamedSharding(
                            mesh, flat_state_partition(zero_specs[n], shp,
                                                       mesh))
                    else:
                        out[n] = dist.state_sharding(mesh, n, shp)
                return out

            from jax.sharding import NamedSharding, PartitionSpec

            def feed_shard(n):
                shp = tuple((feed_shapes or {}).get(n, ()))
                if multi_k:
                    # multi-step scan feeds carry a leading [k] steps axis:
                    # shard the per-step dims per the dist rules and leave
                    # the steps axis unsharded — params/state specs apply
                    # unchanged, so TP placements survive run_steps (a
                    # replicated fallback can OOM exactly where TP rules
                    # exist because params don't fit one device)
                    per_step = dist.feed_sharding(mesh, n, shp[1:])
                    return NamedSharding(
                        mesh, PartitionSpec(None, *per_step.spec))
                return dist.feed_sharding(mesh, n, shp)

            feeds_shard = {n: feed_shard(n) for n in self.feed_names}
            repl = NamedSharding(mesh, PartitionSpec())
            mut_shard = state_shard(self.mut_names)
            ro_shard = state_shard(self.ro_names)
            self.state_shardings = {**mut_shard, **ro_shard}
            jit_kw["in_shardings"] = (mut_shard, ro_shard, feeds_shard, repl)
            # pin written-state outputs to their declared shardings so the
            # arrays written back to the Scope match in_shardings next call
            # (fetches stay unconstrained = None → GSPMD chooses)
            written_shard = state_shard(self.written_state)
            jit_kw["out_shardings"] = ([None] * len(self.fetch_names),
                                       written_shard)
        else:
            self.mesh = None
        self.jitted = jax.jit(fn, donate_argnums=(0,) if donate else (),
                              **jit_kw)

    def _written_persistables(self) -> List[str]:
        written = []
        seen = set()
        for op in self.block.ops:
            for names in op.outputs.values():
                for n in names:
                    if n == "@EMPTY@" or n in seen:
                        continue
                    v = self.block.find_var_recursive(n)
                    if v is not None and v.persistable:
                        written.append(n)
                        seen.add(n)
        return written

    def place_state(self, scope) -> None:
        """Put the scope's state where the jitted step declares it, once,
        before the first call. An array's type carries its mesh: state that
        has never been through this mesh (fresh from the startup program or
        a checkpoint) has another type than the state the step hands back,
        so without this the SECOND call would trace and compile the whole
        step again."""
        for n, want in self.state_shardings.items():
            v = scope.find(n)
            if v is not None and getattr(v, "sharding", None) != want:
                scope.set(n, jax.device_put(v, want))

    def __call__(self, state: dict, feeds: dict, rng_key):
        mut = {n: state[n] for n in self.mut_names}
        ro = {n: state[n] for n in self.ro_names}
        return self.jitted(mut, ro, feeds, rng_key)


class _LocalSGDBlock:
    """LocalSGD train step (reference transpiler/collective.py:270 LocalSGD +
    fleet/meta_optimizers/localsgd_optimizer.py): every dp replica trains its
    OWN parameter copy for k steps, then the copies are averaged.

    TPU-native formulation: the replica copies ARE a tensor axis — every
    written persistable gains a leading [dp] dimension sharded over the
    mesh's dp axis, and the whole train step runs under shard_map so each
    device updates its slice independently. Local steps run an XLA program
    with ZERO cross-replica communication (the point of LocalSGD); every
    k-th step runs a second compilation of the same program with a pmean
    epilogue that averages the copies. Between syncs the Scope keeps the
    last synced (global) view; the diverged copies live under
    '<name>@LOCALSGD' scope entries.
    """

    def __init__(self, program: Program, block_idx: int,
                 feed_names: Sequence[str], fetch_names: Sequence[str],
                 state_names: Sequence[str], k: int):
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.program = program
        self.block = program.blocks[block_idx]
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        self.state_names = list(state_names)
        self.k = int(k)
        self.written_state = _CompiledBlock._written_persistables(self)
        written = set(self.written_state)
        self.mut_names = [n for n in self.state_names if n in written]
        self.ro_names = [n for n in self.state_names if n not in written]
        dist = program._dist_config
        mesh = dist.resolve_mesh()
        self.mesh = mesh
        self.dp = int(mesh.shape["dp"])
        self._step = 0
        self._mut_sharding = NamedSharding(mesh, P("dp"))

        base = functools.partial(_run_block, self.block, self.feed_names,
                                 self.fetch_names, self.mut_names,
                                 self.ro_names, self.written_state)

        def make(sync: bool):
            def inner(mut, ro, feeds, rng):
                mut = {n: v[0] for n, v in mut.items()}   # drop copy axis
                rng = jax.random.fold_in(rng, jax.lax.axis_index("dp"))
                fetches, new_state = base(mut, ro, feeds, rng)
                if sync:
                    new_state = {
                        n: (jax.lax.pmean(v, "dp")
                            if jnp.issubdtype(v.dtype, jnp.floating) else v)
                        for n, v in new_state.items()}
                fetches = [jnp.expand_dims(f, 0) for f in fetches]
                new_state = {n: jnp.expand_dims(v, 0)
                             for n, v in new_state.items()}
                return fetches, new_state

            sm = jax.shard_map(
                inner, mesh=mesh,
                in_specs=({n: P("dp") for n in self.mut_names},
                          {n: P() for n in self.ro_names},
                          {n: P("dp") for n in self.feed_names},
                          P()),
                out_specs=([P("dp")] * len(self.fetch_names),
                           {n: P("dp") for n in self.written_state}),
                check_vma=False)
            return jax.jit(sm, donate_argnums=(0,))

        self._fn_local = make(False)
        self._fn_sync = make(True)
        # sharded tiling: out_shardings makes XLA place one copy per device
        # directly — never materializing all dp copies on a single device
        self._tile = jax.jit(
            lambda v: jnp.broadcast_to(v[None], (self.dp,) + tuple(v.shape)),
            out_shardings=self._mut_sharding)

    def step(self, scope, feeds: dict, rng_key):
        """Returns (fetches, logical_state_updates_for_scope).

        Fetch semantics under localsgd: scalar fetches return the mean over
        replicas (= the global-batch mean for equal shards); non-scalar
        fetches are taken as per-example (batch-leading) and concatenate the
        dp shards back into global batch order.
        """
        import jax.numpy as jnp
        for name, arr in feeds.items():
            if arr.shape and arr.shape[0] % self.dp:
                raise ValueError(
                    f"localsgd: feed {name!r} batch {arr.shape[0]} is not "
                    f"divisible by dp={self.dp}")
        mut = {}
        for n in self.mut_names:
            tiled = scope.find(n + "@LOCALSGD")
            mut[n] = tiled if tiled is not None else self._tile(scope.find(n))
        ro = {n: scope.find(n) for n in self.ro_names}
        # the sync cadence counter lives in the Scope (not on this cache
        # entry): cache misses / multiple fetch signatures share one cadence
        step_idx = int(scope.find("__localsgd_step__") or 0)
        sync = (step_idx % self.k) == self.k - 1
        fn = self._fn_sync if sync else self._fn_local
        fetches, new_tiled = fn(mut, ro, feeds, rng_key)
        scope.set("__localsgd_step__", step_idx + 1)
        for n, v in new_tiled.items():
            scope.set(n + "@LOCALSGD", v)

        def gather(f):
            if f.ndim <= 1:   # stacked scalars: [dp]
                return (f.mean(axis=0)
                        if jnp.issubdtype(f.dtype, jnp.floating) else f[0])
            return f.reshape((f.shape[0] * f.shape[1],) + tuple(f.shape[2:]))

        fetches = [gather(f) for f in fetches]
        logical = {n: v[0] for n, v in new_tiled.items()} if sync else {}
        return fetches, logical


def _buffer_nbytes(block, name, shape) -> int:
    """Size in bytes of a state buffer (donation-floor decisions)."""
    v = block.find_var_recursive(name)
    try:
        itemsize = np.dtype(v.dtype).itemsize if v is not None else 4
    except TypeError:
        itemsize = 4
    n = 1
    for d in shape or ():
        n *= max(int(d), 1)
    return n * itemsize


def _note_time_to_first_step():
    """The operator's own reading of what a restart costs: seconds from the
    OS's creation of the process to the close of its first main-program
    dispatch (no gauge where the OS gives no creation time)."""
    global _first_step_unread
    _first_step_unread = False
    created_us = _trace.process_created_us()
    if created_us is not None:
        _metrics.set_gauge("startup.time_to_first_step_s",
                           (_trace.now_us() - created_us) * 1e-6)


# Stack of programs being traced; sub-block ops (__cond__ etc.) look up their
# sub-blocks through this (trace-time only, never at run time).
_lowering_programs: List = []


def _current_lowering_program():
    return _lowering_programs[-1]


class _LowerTable:
    """Lowering seconds by op type over ONE top-level walk of an op list,
    the `by_op` arg of its `executor.lower_block` span. SELF time by leaf
    type: what a lowering spends in lowerings it runs itself (`__segment__`,
    `__layer_scan__` through `_run_sub_ops`, a sub-block's walk) counts to
    those ops and is taken off the container's row."""

    __slots__ = ("rows", "inner_s", "kept_bytes")
    TOP = 8         # rows the span carries

    def __init__(self):
        self.rows: Dict[str, list] = {}     # type -> [count, self seconds]
        self.inner_s = 0.0          # seconds in lowerings under the open one
        # bytes this walk's recomputed segments keep beside their boundaries
        # (ops/registry.py keep_under_recompute: gauge recompute.kept_bytes)
        self.kept_bytes = 0

    def top(self) -> list:
        rows = sorted(self.rows.items(), key=lambda kv: -kv[1][1])
        return [[t, n, round(sec, 6)] for t, (n, sec) in rows[:self.TOP]]


# The table of the walk in progress, kept like _lowering_programs: trace-time
# only. None outside a walk, and then nothing is timed.
_lower_table: Optional[_LowerTable] = None


@contextlib.contextmanager
def _lower_walk(n_ops, shapes_only):
    """Around a walk of an op list. A top-level walk is one
    `executor.lower_block` span (args `ops`, `shapes_only`, `by_op`: the op
    types with the most lowering seconds as [type, count, seconds]); a walk
    inside a lowering (a `__cond__` branch) adds to the table that is open.
    A context manager and not a wrapper, as `_op_timer` is: no frame of the
    tracing's own stands between the walk and the lowerings, whose every
    primitive carries its Python stack."""
    global _lower_table
    if _lower_table is not None:
        yield
        return
    table = _lower_table = _LowerTable()
    span = _trace.RecordEvent("executor.lower_block", args={
        "ops": n_ops, "shapes_only": bool(shapes_only)})
    try:
        with span:
            try:
                yield
            finally:
                span.add_args(by_op=table.top())
    finally:
        _lower_table = None


class _op_timer:
    """`with _op_timer(op_type, attrs): opdef.lower(...)`: two clock
    readings into the open table (nothing outside a walk). A `__vjp__`
    counts as `grad(<forward type>)`."""

    __slots__ = ("key", "table", "outer_s", "t0")

    def __init__(self, op_type, attrs):
        self.key = (f"grad({attrs.get('fwd_type')})"
                    if op_type == "__vjp__" else op_type)

    def __enter__(self):
        table = self.table = _lower_table
        if table is not None:
            self.outer_s, table.inner_s = table.inner_s, 0.0
            self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        table = self.table
        if table is not None:
            dt = time.perf_counter() - self.t0
            row = table.rows.setdefault(self.key, [0, 0.0])
            row[0] += 1
            row[1] += dt - table.inner_s
            table.inner_s = self.outer_s + dt


def _run_block(block, feed_names, fetch_names, mut_names, ro_names,
               written_state, mut_state: dict, ro_state: dict, feeds: dict,
               rng_key, shapes_only=False):
    """The traced function: sequentially applies each op's lowering over an
    env dict. This is trace-time Python — at run time it is one XLA program.
    `shapes_only` marks a trace whose values nobody runs (jax.eval_shape):
    lowerings that have a cheaper route for that take it."""
    env = dict(ro_state)
    env.update(mut_state)
    env.update(feeds)
    ctx = registry.LowerCtx(rng_key=rng_key, is_eval_shape=shapes_only)
    _lowering_programs.append(block.program)
    try:
        return _run_block_inner(block, fetch_names, written_state, env, ctx)
    finally:
        _lowering_programs.pop()


def _run_block_inner(block, fetch_names, written_state, env, ctx):
    """Walk `block.ops` over `env`: the ONE place every lowering route
    shares (`_run_block`, `_run_block_microbatched`, `parallel/pipeline.py`),
    so the one place that opens `executor.lower_block`. It runs inside the
    traced function: the span exists only while JAX traces, never in a warm
    dispatch."""
    amp_dtype = None
    if getattr(block.program, "_amp", False):
        import jax.numpy as jnp
        amp_dtype = (jnp.bfloat16
                     if getattr(block.program, "_amp_dtype", "bfloat16")
                     == "bfloat16" else jnp.float16)
    with _lower_walk(len(block.ops), ctx.is_eval_shape):
        for op in block.ops:
            opdef = registry.get(op.type)
            ins = {}
            for slot, names in op.inputs.items():
                ins[slot] = [None if n == "@EMPTY@" else env[n]
                             for n in names]
            with op_scopes(op.type, op.attrs), _op_timer(op.type, op.attrs):
                if amp_dtype is not None:
                    ins = _amp_cast(op, ins, amp_dtype)
                outs = opdef.lower(ctx, ins, op.attrs)
            for slot, names in op.outputs.items():
                if slot not in outs:
                    continue
                vals = outs[slot]
                for n, v in zip(names, vals):
                    if n == "@EMPTY@" or v is None:
                        continue
                    env[n] = v
    fetches = [env[n] for n in fetch_names]
    new_state = {n: env[n] for n in written_state if n in env}
    return fetches, new_state


def _run_block_multistep(k_steps, block, feed_names, fetch_names, mut_names,
                         ro_names, written_state, mut_state: dict,
                         ro_state: dict, feeds: dict, rng_key):
    """Device-side training loop: lax.scan over k_steps whole train steps in
    ONE XLA program (one dispatch). The idiomatic TPU loop (the scaling-book
    / MaxText pattern): host dispatch overhead is paid once per k steps, and
    params/optimizer state never leave the device between steps.

    feeds carry a leading [k_steps] axis; each step b draws rng
    fold_in(run_key, b) so dropout differs per step exactly as k separate
    run() calls would differ across their run keys."""
    import jax

    import jax.numpy as jnp

    # Written persistables NOT in the donated mut set must still carry
    # step-to-step. Today that is only vars first materialized by the
    # program itself, absent from the scope entirely (seed zeros; the body
    # overwrites them before any legal read — run() would KeyError on
    # read-before-write anyway): the k-step path donates ALL written state,
    # so the donation floor never routes written names into ro_state here.
    # The ro_state lookup is defensive — if that donation policy ever
    # changes, scope-backed state must seed the carry with its REAL value,
    # and zeros would silently corrupt it (Adam beta-pows). Discover shapes
    # with eval_shape. Carrying beats stacking them as scan ys ([k, ...]
    # HBM for values only [-1] of which is used).
    feeds0 = jax.tree_util.tree_map(lambda a: a[0], feeds)
    _, st_shapes = jax.eval_shape(
        lambda m, f, kk: _run_block(block, feed_names, fetch_names,
                                    mut_names, ro_names, written_state,
                                    m, ro_state, f, kk, shapes_only=True),
        mut_state, feeds0, jax.random.key(0))
    extra0 = {n: (ro_state[n] if n in ro_state
                  else jnp.zeros(s.shape, s.dtype))
              for n, s in st_shapes.items() if n not in mut_state}

    def body(carry, xs):
        mut, extra = carry
        step_feeds, idx = xs
        step_key = jax.random.fold_in(rng_key, idx)
        fetches, new_state = _run_block(
            block, feed_names, fetch_names, mut_names, ro_names,
            written_state, {**mut, **extra}, ro_state, step_feeds, step_key)
        mut2 = {n: new_state.get(n, v) for n, v in mut.items()}
        extra2 = {n: new_state.get(n, v) for n, v in extra.items()}
        return (mut2, extra2), fetches

    xs = (feeds, jnp.arange(k_steps))
    (final_mut, final_extra), stacked_fetches = jax.lax.scan(
        body, (dict(mut_state), extra0), xs, length=k_steps)
    return stacked_fetches, {**final_mut, **final_extra}


def _run_block_microbatched(micro_k, block, feed_names, fetch_names,
                            mut_names, ro_names, written_state,
                            mut_state: dict, ro_state: dict, feeds: dict,
                            rng_key):
    """Pipeline/GPipe train step (reference SectionWorker::TrainFiles,
    framework/section_worker.cc:82-172): LR-sched ops once (:113), then the
    forward+backward ops as one lax.scan over micro_k microbatch slices of
    the feeds accumulating gradients, then the optimizer ops once per mini-
    batch (:172). TPU-native: the whole schedule is a single XLA program —
    the scan bounds activation memory to one microbatch and XLA overlaps
    each microbatch's collectives with the next one's compute.

    Persistable writes from the fwd/bwd section (BN running stats) are
    threaded through the scan carry, so each microbatch sees the previous
    one's running stats — matching sequential-microbatch semantics (the
    reference's per-microbatch scopes share persistables the same way)."""
    import jax
    import jax.numpy as jnp

    sched_ops, body_ops, post_ops = [], [], []
    for op in block.ops:
        role = op.attrs.get("op_role", 0)
        if role == OpRole.LRSched:
            sched_ops.append(op)
        elif role == OpRole.Optimize:
            post_ops.append(op)
        else:
            body_ops.append(op)

    body_produced = set()
    for op in body_ops:
        body_produced.update(op.output_names())
    grad_names = []
    for op in post_ops:
        for n in op.input_names():
            if n in body_produced and n not in grad_names and n != "@EMPTY@":
                grad_names.append(n)
    fetch_in_body = [n for n in fetch_names if n in body_produced]

    env = dict(ro_state)
    env.update(mut_state)
    ctx = registry.LowerCtx(rng_key=rng_key)
    _lowering_programs.append(block.program)
    try:
        # 1) LR-sched once
        pseudo = type(block)(block.program, block.idx, block.parent_idx)
        pseudo.vars = block.vars
        pseudo.ops = sched_ops
        _, _ = _run_block_inner(pseudo, [], [], env, ctx)

        # 2) scan the fwd+bwd section over microbatch slices
        micro_feeds = {}
        for name, arr in feeds.items():
            b = arr.shape[0]
            if b % micro_k:
                raise ValueError(
                    f"pipeline: feed {name!r} batch {b} is not divisible by "
                    f"num_microbatches={micro_k}")
            micro_feeds[name] = jnp.reshape(
                jnp.asarray(arr), (micro_k, b // micro_k) + arr.shape[1:])

        base_env = dict(env)
        body_block = type(block)(block.program, block.idx, block.parent_idx)
        body_block.vars = block.vars
        body_block.ops = body_ops

        # persistables the fwd/bwd section writes (BN running stats): carried
        # through the scan so microbatch i+1 sees microbatch i's update
        body_written = []
        seen = set()
        for op in body_ops:
            for names in op.outputs.values():
                for n in names:
                    if n == "@EMPTY@" or n in seen:
                        continue
                    v = block.find_var_recursive(n)
                    if v is not None and v.persistable and n in base_env:
                        body_written.append(n)
                        seen.add(n)

        def body(carry, mf):
            grad_acc, pers = carry
            step_env = dict(base_env)
            step_env.update(pers)
            step_env.update(mf)
            vals, new_pers = _run_block_inner(
                body_block, grad_names + fetch_in_body, body_written,
                step_env, ctx)
            grads = vals[:len(grad_names)]
            outs = vals[len(grad_names):]
            new_acc = tuple(c + g for c, g in zip(grad_acc, grads))
            pers_carry = {n: new_pers.get(n, pers[n]) for n in body_written}
            return (new_acc, pers_carry), tuple(outs)

        # zero accumulators shaped like one microbatch's grads: get shapes by
        # abstract eval of the first microbatch
        first_mf = {k: v[0] for k, v in micro_feeds.items()}
        shapes = jax.eval_shape(
            lambda e: _run_block_inner(body_block, grad_names, [], dict(e),
                                       ctx)[0],
            {**base_env, **first_mf})
        carry0 = (tuple(jnp.zeros(s.shape, s.dtype) for s in shapes),
                  {n: base_env[n] for n in body_written})

        (acc, pers_final), stacked = jax.lax.scan(body, carry0, micro_feeds)

        # 3) optimizer once on averaged grads; BN stats take their final
        # microbatch value
        env.update(pers_final)
        for n, a in zip(grad_names, acc):
            env[n] = a / micro_k
        for n, s in zip(fetch_in_body, stacked):
            if n in seen:   # body-written persistable: keep its final
                continue    # scan-carry value, not a microbatch average
            env[n] = (jnp.mean(s, axis=0)
                      if jnp.issubdtype(s.dtype, jnp.floating) else s[-1])
        post_block = type(block)(block.program, block.idx, block.parent_idx)
        post_block.vars = block.vars
        post_block.ops = post_ops
        fetches, _ = _run_block_inner(post_block, fetch_names, written_state,
                                      env, ctx)
        new_state = {n: env[n] for n in written_state if n in env}
        return fetches, new_state
    finally:
        _lowering_programs.pop()


def _phase_of_role(role: int) -> str:
    """The phase scope of an op from its `op_role`: the update for
    `Optimize` and `LRSched` (clipping, loss scaling and the finite check
    go where their role puts them), the backward for every `Backward` op
    (`__vjp__`, the repeated gradients' `sum`, the loss gradient's seed),
    else the forward (`Forward`, `Loss`)."""
    if role & (OpRole.Optimize | OpRole.LRSched):
        return _scopes.PHASE_OPT
    if role & OpRole.Backward:
        return _scopes.PHASE_BWD
    return _scopes.PHASE_FWD


# the phase scope that is open, if one is: an op inside a segment or a
# rolled layer is lowered under its container's, which is what runs
_open_phase = None


@contextlib.contextmanager
def op_scopes(op_type, attrs):
    """The two names every instruction of an op carries in the compiled
    step's `op_name` and in a profiler capture: outside, the phase of the
    op's role; inside, its `program.name_scope` (a `__vjp__`'s is its
    forward op's), where it has one. The ONE place both walks open them
    (`_run_block_inner`; `parallel/transforms.py` `_run_sub_ops` for the
    ops inside a segment or a rolled layer, which keep the container's
    phase: a segment the generic `__vjp__` lowers again is backward work),
    around the AMP casts of the op's inputs too. A pullback a `__vjp__` op
    calls (`_segment_grad`) is evaluated under that op's backward scope;
    the forward a checkpoint runs again is named by JAX
    (`observability/scopes.py` `classify`)."""
    global _open_phase
    scope = attrs.get("name_scope") or (
        op_type == "__vjp__" and attrs["fwd_attrs"].get("name_scope"))
    with contextlib.ExitStack() as stack:
        outer = _open_phase
        if outer is None:
            _open_phase = _phase_of_role(attrs.get("op_role", 0))
            stack.enter_context(jax.named_scope(_open_phase))
        if scope:
            stack.enter_context(jax.named_scope(scope))
        try:
            yield
        finally:
            _open_phase = outer


def _amp_cast(op, ins, low_dtype):
    """Static-graph AMP: white-list compute ops run in bf16/fp16, black-list
    ops in f32 (reference contrib/mixed_precision/fp16_utils.py cast
    insertion — here done at lowering time, zero extra graph ops). Grad ops
    (__vjp__) re-derive the policy from their wrapped forward type."""
    op_type = op.attrs.get("fwd_type", op.type) if op.type == "__vjp__" \
        else op.type
    return _amp_cast_ins(op_type, ins, low_dtype)


def _amp_cast_ins(op_type, ins, low_dtype):
    """AMP cast core keyed by resolved forward op type — shared with the
    fused sub-graph lowerings (__segment__/__layer_scan__,
    parallel/transforms.py), whose inner ops must see the same casts the
    top-level op loop applies."""
    import jax.numpy as jnp
    from ..amp.auto_cast import white_list, black_list, keep_f32_slots
    if op_type in white_list:
        target = low_dtype
    elif op_type in black_list:
        target = jnp.float32
    else:
        return ins
    skip = keep_f32_slots.get(op_type, ())
    out = {}
    for slot, vals in ins.items():
        # grad ops see forward slots plus OG:<slot> cotangents and, for an
        # op with a grad rule, FO:<slot> forward outputs; keep all f32 for
        # an excluded slot
        base_slot = slot[3:] if slot.startswith(("OG:", "IG:", "FO:")) \
            else slot
        if base_slot in skip:
            out[slot] = vals
            continue
        out[slot] = [
            v.astype(target)
            if (v is not None and hasattr(v, "dtype")
                and jnp.issubdtype(v.dtype, jnp.floating)
                and v.dtype != target) else v
            for v in vals]
    return out


def _coerce_feed_value(block, name, value):
    """Coercion of ONE feed value (_normalize_feeds): device-side casts for jax
    arrays (feeding device arrays must NOT bounce through host numpy); 64-bit
    ints live as int32 on device (framework/dtype.py policy) with a range
    guard here instead of jax's silent truncation."""
    arr = np.asarray(value) if not hasattr(value, "dtype") else value
    v = block.find_var_recursive(name)
    if v is not None and hasattr(arr, "astype"):
        want = np.dtype(v.dtype)
        if isinstance(arr, jax.Array):
            want = jax.dtypes.canonicalize_dtype(want)
        elif want in (np.dtype(np.int64), np.dtype(np.uint64)):
            # 64-bit-int var: range-check ANY host feed (int64,
            # float64-from-pandas, ...) against the 32-bit device
            # dtype instead of jax's silent wraparound
            info = (np.iinfo(np.int32) if want == np.dtype(np.int64)
                    else np.iinfo(np.uint32))
            if arr.size and (arr.max() > info.max or arr.min() < info.min):
                from .errors import InvalidArgumentError
                raise InvalidArgumentError(
                    f"feed {name!r} holds {want.name} ids outside "
                    f"{info.dtype.name} range; device tensors are "
                    f"32-bit (see framework/dtype.py). Route "
                    f">2B-row ids through distributed_embedding / "
                    f"the sparse KV path, which keeps int64 keys "
                    f"on host.")
            want = np.dtype(info.dtype)
        if np.dtype(arr.dtype) != want:
            arr = arr.astype(want)
    return arr


def _ensure_stacked_params(program, scope):
    """Scope round-trip for rolled-layer programs (apply_layer_scan,
    parallel/transforms.py): whenever all of a stack's per-layer source
    entries are present in the scope — an un-transformed startup program
    ran, or an UNROLLED checkpoint was just loaded — restack them under
    the `<name>@LAYERS` entry the program reads and drop the per-layer
    copies (they are stale the moment training writes the stack). Loaded
    per-layer values therefore always win over a previously stacked
    value, which is what makes old checkpoints load into rolled
    programs."""
    stacks = getattr(program, "_layer_stacks", None)
    if not stacks:
        return
    import jax.numpy as jnp
    for sname, parts in stacks.items():
        if parts and all(scope.has(p) for p in parts):
            scope.set(sname, jnp.stack([jnp.asarray(scope.find(p))
                                        for p in parts]))
            for p in parts:
                scope.erase(p)


def _ensure_shared_beta_pows(program, scope):
    """Legacy-checkpoint adoption for the shared Adam beta-pow pair
    (optimizer.py _create_accumulators): checkpoints written before the
    sharing carry one `<param>_beta{1,2}_pow_acc_0` entry PER PARAM — all
    holding the identical beta^t. When such entries are in the scope (an
    old checkpoint was just loaded; fresh programs never create them),
    adopt their value into the shared var and drop the stale copies, so
    resume keeps the correct bias-correction step instead of silently
    restarting at beta^1. Mirrors _ensure_stacked_params: loaded legacy
    values win; only the program's own RECORDED legacy names are ever
    touched (an exact closed list — O(1) lookups per name, and another
    live program's shared pow var can never be mistaken for legacy
    state). Entries that DISAGREE are left untouched (two legacy
    optimizers with different betas — ambiguous, never guess)."""
    shared = getattr(program, "_shared_beta_pows", None)
    if not shared:
        return
    import jax.numpy as jnp
    gb = program.global_block()
    for sname, legacy_names in shared.items():
        legacy = [n for n in legacy_names
                  if n != sname and not gb.has_var(n) and scope.has(n)]
        if not legacy:
            continue
        vals = [np.asarray(scope.find(n)).reshape(-1) for n in legacy]
        if any(v.shape != (1,) for v in vals):
            continue
        if any(abs(float(v[0]) - float(vals[0][0])) > 1e-12 for v in vals):
            continue        # ambiguous legacy state: adopt nothing
        scope.set(sname, jnp.asarray(vals[0], jnp.float32))
        for n in legacy:
            scope.erase(n)


def _referenced_state_names(block, scope, feed_vals):
    """Persistable vars that already have values in the scope and are
    referenced by this block."""
    referenced = set()
    for op in block.ops:
        referenced.update(op.input_names())
        referenced.update(op.output_names())
    return sorted(
        n for n in referenced
        if n != "@EMPTY@"
        and (v := block.find_var_recursive(n)) is not None
        and v.persistable and scope.has(n) and n not in feed_vals)


def _normalize_feeds(gb, feed, k):
    """The ONE feed normaliser, by `k` (Executor._resolve_call and stage()).
    k=None, the per-step shape: each value coerced to its var's device
    dtype. run_steps(k): a leading [k] steps axis on top of that:
    rank==var rank broadcasts the same batch to every step; rank+1 with
    dim0==k is per-step slices; anything else is ambiguous -> typed error,
    no silent mis-slicing."""
    if k is None:
        return {name: _coerce_feed_value(gb, name, value)
                for name, value in feed.items()}
    import jax.numpy as jnp
    from . import errors
    feed_vals = {}
    for name, value in feed.items():
        arr = jnp.asarray(_coerce_feed_value(gb, name, value))
        v = gb.find_var_recursive(name)
        if v is not None and arr.ndim == len(v.shape) + 1 \
                and arr.shape[0] == k:
            pass                                 # per-step slices
        elif v is None or arr.ndim == len(v.shape):
            arr = jnp.broadcast_to(arr[None], (k,) + tuple(arr.shape))
        else:
            raise errors.InvalidArgument(
                "run_steps feed %r: shape %s matches neither the "
                "per-step var shape %s nor [k=%d] + that shape", name,
                tuple(arr.shape),
                tuple(v.shape) if v is not None else None, k)
        feed_vals[name] = arr
    return feed_vals


def _run_steps_refusal(program):
    """Why run_steps does not take this program, as the typed error to
    raise, or None (Executor.run_steps raises it; train_from_dataset falls
    back to per-step run() on it)."""
    from . import errors
    ps_hooks = getattr(program, "_ps_hooks", None) or []
    if any(not hasattr(h, "pre_multi") for h in ps_hooks):
        return errors.Unimplemented(
            "run_steps with PS hooks that lack window support (e.g. "
            "dense-send hooks); use per-step run()")
    if any(getattr(h, "geo_k", 0) > 0 for h in ps_hooks):
        return errors.Unimplemented(
            "run_steps with Geo-SGD hooks (geo needs per-step local "
            "updates; use per-step run())")
    if getattr(program, "_localsgd_k", 0) or \
            getattr(program, "_microbatch_k", 0):
        return errors.Unimplemented(
            "run_steps with LocalSGD/pipeline programs")
    if _pp_degree(program) > 1:
        return errors.Unimplemented(
            "run_steps over a pp>1 mesh (pipeline stages run per-step)")
    return None


def _pp_degree(program) -> int:
    """Size of the program's mesh along `pp` (1 without a mesh): above 1
    its step is per-stage programs (parallel/pipeline.py), not one block."""
    dist = getattr(program, "_dist_config", None)
    return (int(dist.resolve_mesh().shape.get("pp", 1))
            if dist is not None else 1)


def _make_compiled_block(program, feed_vals, fetch_names, state_names,
                         scope, multi_k=0):
    """The _CompiledBlock constructor call with its shapes and dtypes read
    off the resolved feeds and the scope (Executor._block_for stores it
    into the cache)."""
    compiled = _CompiledBlock(
        program, 0, list(feed_vals), fetch_names, state_names,
        feed_shapes={k: tuple(v.shape) for k, v in feed_vals.items()},
        state_shapes={n: tuple(scope.find(n).shape) for n in state_names},
        multi_k=multi_k,
        feed_dtypes={k: np.asarray(v).dtype if not hasattr(v, "dtype")
                     else v.dtype for k, v in feed_vals.items()},
        state_dtypes={n: scope.find(n).dtype for n in state_names})
    compiled.place_state(scope)
    return compiled


class _StagedFeeds:
    """One pre-staged feed window in the executor's dispatch queue: the
    coerced + device_put'd arrays for a run()/run_steps() call that has not
    been dispatched yet (Executor.stage). Matching is by program identity,
    window size, and VALUE IDENTITY of the original feed objects — the
    caller passes the same arrays (or the staged device dict itself) to the
    consuming run, so a non-matching call simply falls through to normal
    coercion and the entry waits for its owner. `orig_vals` holds STRONG
    references to the originals: identity must be checked with `is`
    against live objects, never a stored id() — a freed original's address
    can be reused by a later unrelated array (CPython id recycling), which
    would silently match a stale window and train on the wrong batch.
    `tag` marks the producer (the device-prefetching DataLoader), so an
    abandoned prefetch iterator can purge ITS pending windows without
    touching manually staged ones."""

    __slots__ = ("prog_key", "k", "orig_vals", "device_feeds", "tag")

    def __init__(self, prog_key, k, orig_vals, device_feeds, tag=None):
        self.prog_key = prog_key
        self.k = k
        self.orig_vals = orig_vals
        self.device_feeds = device_feeds
        self.tag = tag

    def matches(self, program, feed, k) -> bool:
        if self.prog_key != (program._uid, program._version) or self.k != k:
            return False
        if set(feed) != set(self.orig_vals):
            return False
        return all(feed[n] is self.orig_vals[n]
                   or feed[n] is self.device_feeds[n] for n in feed)


class _Call(NamedTuple):
    """What Executor._resolve_call made of one call."""
    fetch_names: list       # the user's, then the PS hooks' gradient fetches
    n_user_fetch: int
    feed_vals: dict         # normalised by k; a staged window's device arrays
    staged: bool            # ... when this is set
    state_names: list
    key: tuple              # the compile-cache key
    ps_hooks: list


def _package_fetches(fetches, fetch_names, return_numpy, sync, step=None):
    """The ONE fetch-return site of Executor._dispatch.

    return_numpy=False: the live device arrays, UNSYNCED — jax dispatch is
    asynchronous, so these may still be computing when returned; the
    consumer's np.asarray (or .block_until_ready) is the sync point, and
    pulling ONE scalar (bench.py _drain) syncs the whole dispatch without
    paying full-tensor D2H. return_numpy=True + sync: the classic drain
    (blocks; counted in executor.host_blocked_ms / fetch_sync_count).
    return_numpy=True + sync=False: lazy FetchHandles (framework/fetch.py)
    that pay the sync only on access — each carries a trace FLOW id opened
    here, closed by its materialization, so the chrome trace links a
    step's dispatch to its (possibly cross-thread, much later) fetch."""
    if not return_numpy:
        return list(fetches)
    if sync:
        from .fetch import _record_sync
        with _trace.RecordEvent("fetch.drain",
                                args={"step": step, "n": len(fetches)}):
            t0 = time.perf_counter()
            out = [np.asarray(f) for f in fetches]
        if out:
            _record_sync(time.perf_counter() - t0, n_values=len(out))
        return out
    from .fetch import FetchHandle
    tracing = _trace.enabled()
    out = []
    for f, n in zip(fetches, fetch_names):
        fid = None
        if tracing:
            fid = _trace.new_flow()
            _trace.flow_start("fetch", fid, args={"name": n, "step": step})
        out.append(FetchHandle(f, name=n, flow=fid))
    return out


class Executor:
    """API-parity with fluid.Executor (reference executor.py:475).

    `place` is accepted for source compatibility; devices are owned by the JAX
    runtime (reference Place/DeviceContext machinery collapses away).

    Host–device overlap surface (docs/perf_notes.md "Host–device overlap"):

    * ``run(..., sync=False)`` / ``FLAGS_async_dispatch`` — lazy fetches:
      FetchHandles that materialize on access instead of draining every
      step (the reference's py_reader/double-buffer philosophy applied to
      the FETCH side).
    * ``stage(feed, ...)`` — pre-coerce + H2D the next window's feeds while
      the current one executes (a depth-1-2 dispatch queue; the reference's
      BufferedReader applied to the FEED side).
    * ``return_numpy=False`` — raw device arrays, unsynced (see
      _package_fetches).
    """

    def __init__(self, place=None):
        import threading
        self.place = place
        self._cache: Dict[tuple, _CompiledBlock] = {}
        # the host-side dispatch queue (stage()): guarded because the
        # device-prefetching DataLoader stages from its fill thread while
        # the training loop consumes on the main thread
        self._staged: "collections.deque[_StagedFeeds]" = collections.deque()
        self._staged_lock = threading.Lock()
        # pod-scope collective correlation plan per compiled program
        # (_emit_collective_markers): (program uid, version) -> ordered
        # [(kind, bucket)] of the program's collective ops
        self._coll_plans: Dict[tuple, list] = {}
        # lazily-created async in-memory snapshotter (resilience/
        # snapshot.py), active only with FLAGS_snapshot_steps > 0;
        # snapshot tags count runs PER PROGRAM (id-keyed)
        self._snapshot_mgr = None
        self._snapshot_prog_steps: Dict[int, int] = {}

    @staticmethod
    def _resolve_sync(sync: Optional[bool]) -> bool:
        """None -> the FLAGS_async_dispatch default. Async always falls
        back to sync while a fault plan is installed: the resilience
        layer's retry/backoff sites reason about materialized host values,
        and the chaos parity contract (scripts/chaos_smoke.py) replays the
        sync path bit-for-bit (counted in executor.async_fallbacks)."""
        from ..flags import flag
        if sync is None:
            sync = not flag("FLAGS_async_dispatch")
        if not sync:
            from ..resilience.faults import current_plan
            if current_plan() is not None:
                monitor.stat_add("executor.async_fallbacks")
                return True
        return bool(sync)

    def stage(self, feed, program: Optional[Program] = None,
              scope: Optional[Scope] = None, k: Optional[int] = None,
              depth: Optional[int] = None, tag=None):
        """Pre-stage the NEXT run()/run_steps() call's feeds: coerce on
        host and start the H2D transfers NOW, while the in-flight window
        still executes — so dispatch time for the next window pays neither.
        With `k`, feeds are normalized to run_steps(k)'s leading [k] axis.

        Donation-aware placement: host arrays device_put into FRESH
        buffers (they cannot alias anything), and a feed value that is
        itself a scope-resident device array is defensively copied — the
        in-flight window may donate that buffer, which would invalidate
        the staged entry before its dispatch (the "donation-vs-staging"
        aliasing rule, docs/perf_notes.md).

        Staged feeds are SNAPSHOTS: the values are coerced and copied to
        device AT STAGE TIME, so mutating the original host buffers in
        place afterwards does not propagate to the staged window (the
        un-staged sync path coerces at run time and WOULD see the
        mutation). Refilling a pinned buffer per batch must therefore
        stage after each refill, never between stage and run.

        Returns the device-feed dict; the queue holds at most
        FLAGS_dispatch_queue_depth windows (oldest dropped — for MANUAL
        staging the latest window wins; the device-prefetching DataLoader
        consumes FIFO and passes `depth` = its buffer depth + 2 so a
        pending window is never evicted before its run). The consuming
        call is matched by program + k + feed-value identity, so pass the
        SAME feed dict (or the returned device dict) to the next run."""
        program = self._resolve_program(program)
        scope = scope or global_scope()
        k = None if k is None else int(k)
        from ..flags import flag
        with _trace.RecordEvent("stage", args={"k": k or 0,
                                               "feeds": len(feed)}):
            t0 = time.perf_counter()
            orig_vals = dict(feed)
            feed_vals = _normalize_feeds(program.global_block(), feed, k)
            import jax.numpy as jnp

            scope_ids = None

            def _all_scope_ids():
                # walk the WHOLE scope chain: donation resolves state
                # through scope.find() (parents included), so a parent-
                # resident buffer needs the defensive copy just as much as
                # a local one. Built LAZILY: only a USER-PROVIDED device
                # array can possibly be scope-resident — the common
                # numpy-feed hot path never pays the O(scope) walk
                ids = set()
                s = scope
                while s is not None:
                    ids.update(id(s.find(n)) for n in s.local_names())
                    s = s.parent
                return ids

            dev = {}
            for n, v in feed_vals.items():
                if isinstance(v, jax.Array):
                    if v is orig_vals.get(n):   # coerced copies are fresh
                        if scope_ids is None:
                            scope_ids = _all_scope_ids()
                        # scope-resident array: copy into a fresh buffer so
                        # the in-flight window's donation cannot invalidate
                        # the staged entry
                        v = jnp.array(v, copy=True) if id(v) in scope_ids \
                            else v
                    dev[n] = v
                else:
                    dev[n] = jax.device_put(v)
            monitor.stat_add("executor.h2d_ms",
                             (time.perf_counter() - t0) * 1000.0)
        if depth is None:
            depth = int(flag("FLAGS_dispatch_queue_depth"))
        depth = max(1, int(depth))
        with self._staged_lock:
            # the depth bound is PER TAG: manual staging (tag=None,
            # latest-wins) must never evict a prefetch iterator's pending
            # FIFO windows staged under its own larger bound, and vice
            # versa — each producer only trims its own entries
            same = [e for e in self._staged if e.tag is tag]
            while len(same) >= depth:
                self._staged.remove(same.pop(0))
            self._staged.append(_StagedFeeds(
                (program._uid, program._version), k, orig_vals, dev,
                tag=tag))
            monitor.stat_set("executor.dispatch_queue_depth",
                             len(self._staged))
        return dev

    def _purge_staged(self, tag):
        """Drop every staged window carrying `tag` (an abandoned
        device-prefetching iterator's pending H2D buffers must not pin
        HBM for the rest of the process)."""
        with self._staged_lock:
            kept = [e for e in self._staged if e.tag is not tag]
            if len(kept) != len(self._staged):
                self._staged = collections.deque(kept)
                monitor.stat_set("executor.dispatch_queue_depth",
                                 len(self._staged))

    def _take_staged(self, program, feed, k):
        """Pop and return the staged device feeds matching this call (or
        None). Non-matching entries stay queued for their owner."""
        with self._staged_lock:
            for i, e in enumerate(self._staged):
                if e.matches(program, feed, k):
                    del self._staged[i]
                    monitor.stat_set("executor.dispatch_queue_depth",
                                     len(self._staged))
                    return e.device_feeds
        return None

    def _resolve_staged_donation(self, compiled, staged_vals, scope):
        """Donation-conflict resolution for consumed staged feeds: any
        staged buffer that IS a scope buffer the block donates gets a
        device-side copy BEFORE dispatch (the donation would invalidate
        the feed's backing array mid-step — flipping fetch mode alone
        would not help; only a fresh buffer does). stage() already copies
        scope-resident values, so this only fires when state was
        re-pointed at a staged array after staging. Returns
        (feed_vals, n_conflicts), the conflicts counted and marked in the
        trace; the dispatch also falls back to sync when n_conflicts > 0
        (the conservative serialization the docs promise). Covers the
        LocalSGD path's `<name>@LOCALSGD` entries too — every block class
        donates its mut set."""
        mut_names = getattr(compiled, "mut_names", None)
        if not mut_names:
            return staged_vals, 0
        mut_ids = set()
        for n in mut_names:
            for cand in (scope.find(n), scope.find(n + "@LOCALSGD")):
                if cand is not None:
                    mut_ids.add(id(cand))
        if not any(id(v) in mut_ids for v in staged_vals.values()):
            return staged_vals, 0
        import jax.numpy as jnp
        out, n_conf = {}, 0
        for name, v in staged_vals.items():
            if id(v) in mut_ids:
                out[name] = jnp.copy(v)
                n_conf += 1
            else:
                out[name] = v
        monitor.stat_add("executor.staging_conflicts", n_conf)
        _trace.instant("donation_conflict_copy",
                       args={"n": n_conf, "step": self._step_counter})
        return out, n_conf

    def run(self, program: Optional[Program] = None, feed: Optional[dict] = None,
            fetch_list: Optional[list] = None, scope: Optional[Scope] = None,
            return_numpy: bool = True, use_program_cache: bool = True,
            sync: Optional[bool] = None):
        """Run the program's global block once.

        Fetch semantics (docs/perf_notes.md "Host–device overlap"):

        * ``return_numpy=True, sync=True`` (default): fetches drain to
          numpy — a full device sync + D2H every call.
        * ``return_numpy=True, sync=False`` (or ``FLAGS_async_dispatch``):
          fetches are lazy FetchHandles; the sync + D2H happens per handle
          on first access. State writes are unaffected either way — the
          Scope adopts the step's device buffers without draining them.
        * ``return_numpy=False``: the live device arrays, UNSYNCED — jax
          dispatch is async, so they may still be computing; np.asarray
          (or .block_until_ready) at the consumer is the sync point.
        """
        return self._dispatch(None, program, feed, fetch_list, scope,
                              return_numpy, use_program_cache, sync)

    @staticmethod
    def _resolve_program(program):
        program = program or default_main_program()
        if hasattr(program, "_is_data_parallel"):   # CompiledProgram shim
            program = program.program
        return program

    @contextlib.contextmanager
    def _step_window(self, kind, program, k=1):
        """One executor step: advance the counter, bracket the flight-
        recorder window, open the ROOT span `executor.step` (args: step,
        exe, kind "run" | "run_steps", k, program "startup" | "main", ops)
        under which every span of this dispatch lies, and fire the
        FLAGS_profile_start/stop_step triggers. Shared by run() AND
        run_steps() so a mixed loop (e.g. train_from_dataset dispatching
        full groups via run_steps and tail batches via run) sees every
        counter value exactly once — an equality trigger can never be
        skipped."""
        from .. import profiler as _prof
        from ..flags import flag
        self._step_counter = getattr(self, "_step_counter", 0) + 1
        idx = self._step_counter
        # flight windows are keyed (owner, idx): every Executor restarts
        # its counter at 1, so a train+eval pair needs distinct owners
        owner = getattr(self, "_flight_owner", None)
        if owner is None:
            owner = self._flight_owner = next(_flight_owner_ids)
        if idx == flag("FLAGS_profile_start_step"):
            _prof.start_profiler()
        _flight.begin_step(idx, owner=owner)
        status = "ok"
        is_startup = program is default_startup_program()
        root = _trace.RecordEvent("executor.step", args={
            "step": idx, "exe": owner, "kind": kind, "k": k,
            "program": "startup" if is_startup else "main",
            "ops": op_count(program)})
        try:
            with root:
                yield idx
        except BaseException:
            status = "error"
            raise
        finally:
            if _first_step_unread and not is_startup:
                _note_time_to_first_step()
            _flight.end_step(idx, status=status, owner=owner)
            if idx == flag("FLAGS_profile_stop_step"):
                _prof.stop_profiler()

    def _maybe_snapshot(self, program, scope):
        """Post-step snapshot hook (FLAGS_snapshot_steps cadence). Grabs
        array REFERENCES on the hot path — jax arrays are immutable, so
        the device->host copy itself runs on the snapshotter's thread —
        and installs the SIGTERM grace-window flush on first use."""
        from ..flags import flag
        interval = int(flag("FLAGS_snapshot_steps") or 0)
        if interval <= 0:
            return
        if self._snapshot_mgr is None:
            from ..resilience.snapshot import SnapshotManager
            self._snapshot_mgr = SnapshotManager(interval=interval)
            self._snapshot_mgr.install_sigterm_flush()
        # Tag with THIS program's run count, not the executor-wide step
        # counter: that counter also ticks for the startup program and
        # any eval program, so its value is shifted against the trainer's
        # own step indexing — and a recover()ed tag must map onto the
        # batch schedule for restore-and-replay to be bit-identical.
        counts = self._snapshot_prog_steps
        key = id(program)
        counts[key] = counts.get(key, 0) + 1
        self._snapshot_mgr.maybe_capture(program, scope, counts[key])

    @property
    def snapshots(self):
        """The live SnapshotManager (None until the first snapshotted
        step) — trainers hand it to TrainingGuard / DivergenceSentinel."""
        return self._snapshot_mgr

    def _resolve_call(self, program, feed, fetch_list, scope, k,
                      take_staged=True) -> _Call:
        """THE resolver: which compiled block a call means (k=None: the
        per-step shape; k: run_steps(k)'s). Nothing else names the fetches,
        normalises feeds, adopts loaded state, collects the referenced
        state or knows the shape of the compile-cache key, so a dispatch
        and compiled_hlo()/step_jaxpr() of the same signature cannot mean
        different blocks. Inspection passes take_staged=False: a staged
        window waits for the dispatch that owns it."""
        from . import errors
        feed = feed or {}
        gb = program.global_block()
        fetch_names = [v.name if isinstance(v, Variable) else str(v)
                       for v in fetch_list or []]
        for n in fetch_names:
            if not gb.has_var(n):
                raise errors.NotFound(
                    "fetch target %r is not a variable of this program", n,
                    var=n)
        n_user_fetch = len(fetch_names)
        # staged windows match the USER feed — before PS hooks add their
        # pulled-row keys, which stage() never saw (a post-hook match
        # would always miss on PS programs and silently double the H2D)
        staged_vals = self._take_staged(program, feed, k) if take_staged \
            else None
        # parameter-server hooks (distributed_embedding): pull sparse rows
        # before the step, push their grads after (distributed/ps.py). In
        # a k-step window: ONE pull covering all k batches' ids, ONE summed
        # push after — the reference's async-communicator batching
        # (communicator.h), amortizing dispatch + RPC cost over k
        ps_hooks = getattr(program, "_ps_hooks", None) or []
        if ps_hooks:
            feed = dict(feed)
            for h in ps_hooks:
                feed.update(h.pre(feed) if k is None else h.pre_multi(feed))
                if gb.has_var(h.grad_name) and \
                        h.grad_name not in fetch_names:
                    fetch_names.append(h.grad_name)
        # a staged window paid coercion + H2D in stage(); only hook-added
        # entries (the pulled rows) still normalise here
        feed_vals = dict(staged_vals or {})
        feed_vals.update(_normalize_feeds(
            gb, {n: v for n, v in feed.items() if n not in feed_vals}, k))
        # adoption BEFORE the state names: it renames scope entries the
        # program reads (a loaded checkpoint's per-layer / per-param /
        # unsharded entries become the stacked / shared / flat ones)
        _ensure_stacked_params(program, scope)
        _ensure_shared_beta_pows(program, scope)
        from ..parallel.zero import adopt_unsharded_state
        adopt_unsharded_state(program, scope)
        state_names = _referenced_state_names(gb, scope, feed_vals)
        feed_spec = tuple(sorted((n, tuple(v.shape), str(v.dtype))
                                 for n, v in feed_vals.items()))
        key = (program._uid, program._version, feed_spec,
               tuple(fetch_names), tuple(state_names))
        if k is not None:
            key = ("multi", k) + key
        return _Call(fetch_names, n_user_fetch, feed_vals,
                     staged_vals is not None, state_names, key, ps_hooks)

    def _block_for(self, program, call, scope, k, use_program_cache=True):
        """The ONE place that finds a resolved call's block in the cache
        (`executor.compile_cache_hits`) or builds it (`_misses`): a `jax.jit`
        object with state placed on the mesh; the first launch compiles."""
        compiled = self._cache.get(call.key) if use_program_cache else None
        if compiled is not None:
            _metrics.inc("executor.compile_cache_hits")
            return compiled
        _metrics.inc("executor.compile_cache_misses")
        with _trace.RecordEvent("executor.build_block"):
            localsgd_k = getattr(program, "_localsgd_k", 0)
            if _pp_degree(program) > 1:
                # the pp mesh axis engages true pipeline parallelism:
                # stages partitioned by device_guard, placed on pp
                # submeshes (parallel/pipeline.py)
                if localsgd_k and localsgd_k > 1:
                    from . import errors
                    raise errors.Unimplemented(
                        "LocalSGD over a pp>1 mesh (pipeline stages "
                        "and per-replica parameter copies are "
                        "incompatible)")
                from ..parallel.pipeline import _PipelineBlock
                compiled = _PipelineBlock(program, 0, list(call.feed_vals),
                                          call.fetch_names, call.state_names)
            elif localsgd_k and localsgd_k > 1:
                compiled = _LocalSGDBlock(program, 0, list(call.feed_vals),
                                          call.fetch_names, call.state_names,
                                          localsgd_k)
            else:
                compiled = _make_compiled_block(
                    program, call.feed_vals, call.fetch_names,
                    call.state_names, scope, multi_k=k or 0)
        if use_program_cache:
            self._cache[call.key] = compiled
        return compiled

    def _dispatch(self, k, program, feed, fetch_list, scope, return_numpy,
                  use_program_cache, sync):
        """THE dispatch body: run() is this with k=None, run_steps(k) its
        refusals and this with k (ONE dispatch: the step counter advances
        once, the root carries k). Under the root `executor.step` exactly
        one `executor.prepare` (resolve the call, its block, the rng draw),
        `executor.launch` (the jitted call) and `executor.commit` (state
        into the scope, hooks, fetches packaged)."""
        from ..flags import flag
        program = self._resolve_program(program)
        with self._step_window("run" if k is None else "run_steps", program,
                               k or 1) as step_idx:
            with _trace.RecordEvent("executor.prepare"):
                scope = scope or global_scope()
                sync = self._resolve_sync(sync)
                call = self._resolve_call(program, feed, fetch_list, scope, k)
                compiled = self._block_for(program, call, scope, k,
                                           use_program_cache)
                feed_vals = call.feed_vals
                if call.staged:
                    # the donation-vs-staging aliasing rule: a staged
                    # buffer the step donates is copied into a fresh buffer
                    # pre-dispatch, and the call serializes (sync) for good
                    # measure
                    feed_vals, n_conf = self._resolve_staged_donation(
                        compiled, feed_vals, scope)
                    sync = sync or n_conf > 0
                rng_key = _next_rng_key(scope, program.random_seed)
                # _LocalSGDBlock / _PipelineBlock drive the scope themselves
                launch = (functools.partial(
                    compiled, {n: scope.find(n) for n in call.state_names},
                    feed_vals, rng_key)
                    if isinstance(compiled, _CompiledBlock)
                    else functools.partial(compiled.step, scope, feed_vals,
                                           rng_key))
                # step-level hang watchdog: bound the dispatch (and, below,
                # the synchronous fetch drain) so a wedged collective —
                # inside a k-step scan just the same — surfaces as a typed
                # error the gang supervisor can restart on, never a hang
                step_deadline = float(flag("FLAGS_step_deadline_ms") or 0.0)
                self._emit_collective_markers(program, step_idx, k)
            with _trace.RecordEvent("executor.launch"):
                if step_deadline > 0:
                    what = "step" if k is None else f"run_steps(k={k})"
                    fetches, new_state = _deadline_call(
                        launch, step_deadline,
                        f"{what} dispatch ({op_count(program)} ops)")
                else:
                    fetches, new_state = launch()
            with _trace.RecordEvent("executor.commit"):
                for n, v in new_state.items():
                    scope.set(n, v)
                self._maybe_snapshot(program, scope)
                if flag("FLAGS_check_nan_inf"):
                    # run_steps' stacked [k, ...] fetches scan the same way:
                    # a NaN in any of the k steps names its variable
                    _check_nan_inf(dict(zip(call.fetch_names, fetches)),
                                   new_state)
                if call.ps_hooks:
                    fetched_by_name = dict(zip(call.fetch_names, fetches))
                    for h in call.ps_hooks:
                        (h.post if k is None else h.post_multi)(
                            fetched_by_name)
                    fetches = fetches[:call.n_user_fetch]
                user_names = call.fetch_names[:call.n_user_fetch]
                if k is None and not sync and return_numpy and fetches:
                    # lazy-fetch side of the donation rule: a fetch of a
                    # WRITTEN persistable shares (or may share) the buffer
                    # the scope just adopted — the NEXT dispatch donates
                    # that buffer, and a deferred .numpy() would read
                    # deleted memory. Snapshot those rare fetches with a
                    # device-side copy (bit-identical, async); ordinary
                    # fetches pass through untouched. The sync path is
                    # immune (it drains before any next dispatch). k=None
                    # ONLY: a run_steps fetch is a fresh stacked [k, ...]
                    # buffer sharing nothing with the scope, and a copy of
                    # it a device copy the step does not need.
                    import jax.numpy as jnp
                    fetches = [jnp.copy(f)
                               if (n in new_state and hasattr(f, "dtype"))
                               else f for f, n in zip(fetches, user_names)]

                def package():
                    return _package_fetches(fetches, user_names, return_numpy,
                                            sync, step=step_idx)
                if step_deadline > 0 and sync and return_numpy:
                    return _deadline_call(package, step_deadline,
                                          "fetch materialization")
                return package()

    def _collective_marker_plan(self, program) -> list:
        """Ordered [(kind, bucket_index)] of the program's collective ops —
        the per-dispatch correlation plan for pod-scope tracing. Manual-dp
        programs enumerate their explicit `__bucket_sync__` /
        `__zero_update__` / `__zero_gather__` / `__zero_pack__` ops in
        program order (identical across gang ranks, so (step, bucket, seq)
        keys match rank-to-rank); a GSPMD multi-device program, whose
        collectives are implicit in the lowering, gets one `__step_sync__`
        marker per dispatch so cross-rank step arrows still link."""
        key = (program._uid, program._version)
        plan = self._coll_plans.get(key)
        if plan is None:
            from ..analysis.collectives import COLLECTIVE_OPS
            plan = []
            per_kind: Dict[str, int] = {}
            for block in program.blocks:
                for op in block.ops:
                    if op.type in COLLECTIVE_OPS:
                        b = per_kind.get(op.type, 0)
                        per_kind[op.type] = b + 1
                        plan.append((op.type, b))
            if not plan:
                dist = getattr(program, "_dist_config", None)
                if dist is not None:
                    try:
                        shape = dist.resolve_mesh().shape
                        ndev = 1
                        for v in shape.values():
                            ndev *= int(v)
                    except Exception:
                        ndev = 1
                    if ndev > 1:
                        plan = [("__step_sync__", 0)]
            self._coll_plans[key] = plan
        return plan

    def _emit_collective_markers(self, program, step_idx, k=None):
        """Stamp one correlation-key instant per collective op at dispatch
        (cat "collective", args {kind, step, bucket, seq, key}). The ts is
        the HOST DISPATCH time — the step is one XLA program, so this is
        the rank's arrival at the step's collectives, the quantity the
        pod-scope merge compares across ranks (who stalled whom). A few
        trace-ring appends per step; nothing when tracing is off."""
        from ..flags import flag
        if not (_trace.enabled() and flag("FLAGS_collective_markers")):
            return
        for seq, (kind, bucket) in enumerate(
                self._collective_marker_plan(program)):
            args = {"kind": kind, "step": int(step_idx), "bucket": bucket,
                    "seq": seq, "key": f"s{int(step_idx)}.b{bucket}.q{seq}"}
            if k:
                args["k"] = int(k)
            _trace.instant("collective", args=args, cat="collective")

    def annotate_step_cost(self, feed=None, fetch_list=None, program=None,
                           scope=None, k=None) -> dict:
        """XLA's cost analysis (flops, bytes accessed) + CompiledMemoryStats
        (argument/output/temp bytes) of the jitted step for this signature,
        via _inspect_compiled (sharing run()'s compile cache), as a dict.
        The fields the backend cannot report are simply absent (CPU-mesh
        XLA reports flops; memory stats availability varies by version).
        XLA's counts are no source for a roofline (PERF.md section 6):
        nothing is recorded from them."""
        compiled = self._inspect_compiled(feed, fetch_list, program, scope, k)
        cost: dict = {}
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            for src, dst in (("flops", "device_flops"),
                             ("bytes accessed", "device_bytes_accessed")):
                v = ca.get(src)
                if v is not None:
                    cost[dst] = float(v)
        except Exception:
            pass
        try:
            ma = compiled.memory_analysis()
            for attr, dst in (("argument_size_in_bytes", "argument_bytes"),
                              ("output_size_in_bytes", "output_bytes"),
                              ("temp_size_in_bytes", "temp_bytes")):
                v = getattr(ma, attr, None)
                if v is not None:
                    cost[dst] = int(v)
        except Exception:
            pass
        return cost

    def run_steps(self, k: int, program: Optional[Program] = None,
                  feed: Optional[dict] = None,
                  fetch_list: Optional[list] = None,
                  scope: Optional[Scope] = None, return_numpy: bool = True,
                  sync: Optional[bool] = None):
        """Run `k` train steps as ONE device dispatch (a lax.scan training
        loop inside a single XLA program — the scaling-book/MaxText loop).

        `feed` arrays either carry a leading [k] axis (per-step slices) or
        per-step shapes (broadcast: every step sees the same batch).
        Fetches come back stacked over steps ([k, ...] each). Parameters and
        optimizer state stay device-resident across all k steps, and host
        dispatch cost is paid once. Random ops draw a distinct key per step
        (fold_in of the run key), matching k separate run() calls in
        distribution. Fetch semantics match run(): sync=False (or
        FLAGS_async_dispatch) returns lazy FetchHandles over the stacked
        device arrays; return_numpy=False returns them unsynced — so a
        window loop that only logs every few windows never blocks the
        host between dispatches. Sparse-PS programs run in WINDOW mode: one KV pull
        covering all k batches' ids, rows frozen for the window, one summed
        push after (_PsHook.pre_multi/post_multi — the reference's async
        communicator batching). Not supported: Geo-SGD or dense-send hooks,
        pipeline / LocalSGD programs, heter sections."""
        from . import errors
        if not isinstance(k, (int, np.integer)) or k < 1:
            raise errors.InvalidArgument(
                "run_steps needs an integer k >= 1, got %r", k)
        program = self._resolve_program(program)
        refusal = _run_steps_refusal(program)
        if refusal is not None:
            raise refusal
        return self._dispatch(int(k), program, feed, fetch_list, scope,
                              return_numpy, True, sync)

    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           steps_per_loop=1):
        """Drain one epoch of a fluid.dataset through the jitted train step
        (reference executor.py:1598 -> TrainerFactory/MultiTrainer threads).

        The data plane OVERLAPS the device: a producer thread iterates the
        dataset (MultiSlot parse/pack runs there) into a bounded queue while
        the main thread dispatches steps with device-resident fetches —
        jax dispatch is async, so step N computes while batch N+1 parses.
        This is the reference Trainer/DeviceWorker design's purpose
        (trainer.h:51: keep the device busy) in two threads + XLA async
        dispatch instead of a DeviceWorker pool.

        `steps_per_loop > 1` groups that many uniform-shape batches into
        ONE run_steps dispatch (the device-side scan loop) — same numbers,
        1/k the dispatch cost; odd-shaped tails and the final partial
        group fall back to per-step run(). Ignored for PS/pipeline/
        LocalSGD programs, which run_steps does not take."""
        assert dataset is not None, "train_from_dataset needs a dataset"
        import queue as _queue
        import threading

        program = program or default_main_program()
        fetch_list = fetch_list or []
        q: "_queue.Queue" = _queue.Queue(maxsize=4)
        _END = object()
        err = []
        stop = threading.Event()

        def _produce():
            try:
                for feed in dataset:
                    while not stop.is_set():
                        try:
                            q.put(feed, timeout=0.2)
                            break
                        except _queue.Full:
                            continue
                    if stop.is_set():
                        return
            except BaseException as e:   # surface parse errors in the main
                err.append(e)            # thread, not a dead daemon
            finally:
                # the sentinel must not be lost when the queue is full and
                # the consumer is still draining — block until it fits (or
                # the consumer has signalled stop, in which case nobody is
                # waiting on it)
                while not stop.is_set():
                    try:
                        q.put(_END, timeout=0.2)
                        break
                    except _queue.Full:
                        continue

        producer = threading.Thread(target=_produce, daemon=True,
                                    name="dataplane-prefetch")
        producer.start()
        fetched = None
        step = 0
        group_k = int(steps_per_loop)
        if group_k > 1 and _run_steps_refusal(
                self._resolve_program(program)) is not None:
            # e.g. geo / dense-send hooks need per-step pull-push; sparse
            # window hooks ride the grouped run_steps path (pre_multi/
            # post_multi)
            group_k = 1

        def _shapes(feed):
            return {k: np.shape(v) for k, v in feed.items()}

        def _debug_print(vals, n_done=1):
            # grouped mode: fire when the group CROSSED a print_period
            # boundary, labelled with the step the values belong to (the
            # group's last)
            crossed = (step == 0
                       or step // print_period
                       != (step + n_done) // print_period)
            if debug and fetch_list and crossed:
                names = fetch_info or [getattr(v, "name", str(v))
                                       for v in fetch_list]
                print(f"step {step + n_done - 1}: " + ", ".join(
                    f"{n}={np.asarray(v).ravel()[:4]}"
                    for n, v in zip(names, vals)))

        buf = []

        def _flush():
            nonlocal fetched, step
            if not buf:
                return
            if len(buf) < group_k:
                # tail / odd group: per-step run() — no extra scan compile
                # for a one-off size
                for f in buf:
                    fetched = self.run(program=program, feed=f,
                                       fetch_list=fetch_list, scope=scope,
                                       return_numpy=False)
            else:
                stacked = {k: np.stack([np.asarray(f[k]) for f in buf])
                           for k in buf[0]}
                stacked_fetch = self.run_steps(
                    len(buf), program=program, feed=stacked,
                    fetch_list=fetch_list, scope=scope, return_numpy=False)
                fetched = [v[-1] for v in stacked_fetch]
            _debug_print(fetched, n_done=len(buf))
            step += len(buf)
            buf.clear()

        try:
            while True:
                feed = q.get()
                if feed is _END:
                    break
                if group_k <= 1:
                    # return_numpy=False: dispatch without blocking on the
                    # result — only debug prints (and the final return)
                    # materialize to host
                    fetched = self.run(program=program, feed=feed,
                                       fetch_list=fetch_list, scope=scope,
                                       return_numpy=False)
                    _debug_print(fetched)
                    step += 1
                    continue
                if buf and _shapes(buf[0]) != _shapes(feed):
                    _flush()          # odd-shaped batch breaks the group
                buf.append(feed)
                if len(buf) == group_k:
                    _flush()
            _flush()                  # the final partial group
        finally:
            # a failed step must not leave the producer blocked on the
            # bounded queue holding the dataset open: signal + drain
            stop.set()
            while True:
                try:
                    q.get_nowait()
                except _queue.Empty:
                    break
            producer.join(timeout=10)
        if err:
            raise err[0]
        if fetched is not None:
            fetched = [np.asarray(f) for f in fetched]
        return fetched

    def compiled_hlo(self, feed=None, fetch_list=None, program=None,
                     scope=None, k=None):
        """Optimized-HLO text of the jitted step for this (feed, fetch)
        signature — the PUBLIC surface for compile-stats tooling
        (scripts/collective_audit.py, scripts/copy_audit.py, HLO-structure
        tests) that previously poked `exe._cache` internals. Shares run()'s
        compile cache (same key), so calling after run() reuses the
        compiled block and calling before run() pre-populates it. With
        `k`, the run_steps(k) device-side training-loop program is lowered
        instead (same cache as run_steps — the copy/collective census of
        the k-step dispatch is what executes on hardware). The program is
        only lowered and compiled, never executed: donation marks do not
        consume the scope's buffers. Requires initialized state (run the
        startup program first); pipeline/LocalSGD/PS programs are not
        supported — their steps are not one jitted computation."""
        return self._inspect_compiled(feed, fetch_list, program, scope,
                                      k).as_text()

    def compiled_memory_analysis(self, feed=None, fetch_list=None,
                                 program=None, scope=None, k=None):
        """XLA's CompiledMemoryStats for the jitted step (per-DEVICE
        argument/output/temp bytes) — the structural memory surface behind
        the ZeRO-1 optimizer-state checks (tests/test_collective_budget.py,
        bench.py extras): dp-sharded flat state shows up as
        argument bytes divided by dp, with no wall-clock involved. Same
        cache/signature rules as compiled_hlo."""
        return self._inspect_compiled(feed, fetch_list, program, scope,
                                      k).memory_analysis()

    def step_jaxpr(self, feed=None, fetch_list=None, program=None,
                   scope=None, k=None):
        """The jitted step as a ClosedJaxpr, before XLA: what the op
        lowerings traced to, kernel calls by name included (a Pallas kernel
        is a `pallas_call` equation here on every backend, and a Mosaic
        custom call only in a TPU's HLO). Same signature and cache rules as
        compiled_hlo; traced only, never compiled or run."""
        return self._inspect_traced(feed, fetch_list, program, scope,
                                    k).jaxpr

    def _inspect_compiled(self, *signature):
        return self._inspect_traced(*signature).lower().compile()

    def _inspect_traced(self, feed, fetch_list, program, scope, k):
        import jax.numpy as jnp

        from . import errors
        program = self._resolve_program(program)
        if getattr(program, "_ps_hooks", None) \
                or getattr(program, "_localsgd_k", 0) \
                or _pp_degree(program) > 1:
            raise errors.Unimplemented(
                "compiled_hlo on PS/LocalSGD programs or over a pp>1 mesh "
                "(their step is not one jitted computation)")
        if k is not None:
            if isinstance(k, bool) or not isinstance(k, (int, np.integer)) \
                    or k < 1:
                raise errors.InvalidArgument(
                    "compiled_hlo k=%r: needs an integer k >= 1", k)
            if getattr(program, "_microbatch_k", 0):
                raise errors.Unimplemented(
                    "compiled_hlo k=%d on a pipeline (microbatched) "
                    "program — run_steps does not take those", int(k))
            k = int(k)
        scope = scope or global_scope()
        call = self._resolve_call(program, feed, fetch_list, scope, k,
                                  take_staged=False)
        compiled = self._block_for(program, call, scope, k)
        if not isinstance(compiled, _CompiledBlock):
            raise errors.Unimplemented(
                "compiled_hlo: cached entry for this signature is not a "
                "single jitted block")
        mut = {n: scope.find(n) for n in compiled.mut_names}
        ro = {n: scope.find(n) for n in compiled.ro_names}
        feeds = {n: jnp.asarray(v) for n, v in call.feed_vals.items()}
        return compiled.jitted.trace(mut, ro, feeds, jax.random.key(0))

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        return self.train_from_dataset(program, dataset, scope, thread,
                                       debug, fetch_list, fetch_info,
                                       print_period)

    def close(self):
        self._cache.clear()
        with self._staged_lock:
            self._staged.clear()
            monitor.stat_set("executor.dispatch_queue_depth", 0)
        if self._snapshot_mgr is not None:
            self._snapshot_mgr.close()
            self._snapshot_mgr = None


def op_count(program) -> int:
    return sum(len(b.ops) for b in program.blocks)


def _dump_thread_stacks() -> str:
    """Stacks of every live thread — the watchdog's post-mortem payload:
    WHICH thread is wedged, and where (typically a collective blocked in C
    on a dead peer)."""
    import sys as _sys
    import threading
    import traceback
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for tid, frame in _sys._current_frames().items():
        out.append(f"--- thread {names.get(tid, '?')} ({tid}) ---\n"
                   + "".join(traceback.format_stack(frame)))
    return "".join(out)


def _deadline_call(fn, deadline_ms: float, what: str):
    """Step-level hang watchdog (FLAGS_step_deadline_ms): run `fn` on a
    worker thread and join with the deadline. On a pod, one dead host
    leaves every survivor's next collective blocked in C forever — a state
    the gang supervisor (distributed/launch.py) can only act on if the
    worker FAILS, so a trip raises the typed DeadlineExceededError
    carrying a full thread-stack dump (counted in
    `executor.step_deadline_trips`) instead of hanging. The abandoned
    worker thread cannot be cancelled and keeps blocking (daemon): after a
    trip this process's step state is indeterminate — the caller is
    expected to checkpoint-from-last-complete and exit/restart, which is
    exactly the supervisor's elastic-restart contract."""
    import threading
    from . import errors
    result = {}

    def target():
        try:
            result["value"] = fn()
        except BaseException as e:        # re-raised on the caller thread
            result["error"] = e

    t = threading.Thread(target=target, daemon=True, name="executor-step")
    t.start()
    t.join(deadline_ms / 1000.0)
    if t.is_alive():
        monitor.stat_add("executor.step_deadline_trips")
        stacks = _dump_thread_stacks()
        # the flight recorder ships the wedge's own timeline: last-N step
        # spans + metric deltas land next to the thread-stack dump, so the
        # postmortem does not have to be reconstructed from prints
        dump_path = _flight.dump(
            "step_deadline",
            extra={"what": what, "deadline_ms": deadline_ms,
                   "thread_stacks": stacks})
        raise errors.DeadlineExceeded(
            "%s exceeded FLAGS_step_deadline_ms=%.0f (wedged collective / "
            "dead peer?); flight-recorder dump: %s; thread stacks:\n%s",
            what, deadline_ms, dump_path or "<disabled>", stacks)
    if "error" in result:
        raise result["error"]
    return result["value"]


def _check_nan_inf(fetched: dict, new_state: dict):
    """FLAGS_check_nan_inf (reference operator.cc:1129 post-op scan +
    nan_inf_utils_detail.cc). The block runs as one fused program, so the
    scan covers its observable outputs: fetches + written state, reported by
    variable name."""
    import jax.numpy as jnp
    from ..flags import flag
    bad = []
    for group in (fetched, new_state):
        for n, v in group.items():
            if hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.floating):
                if not bool(jnp.isfinite(v).all()):
                    bad.append(n)
    if bad:
        msg = (f"NaN/Inf detected in variables {bad} "
               "(FLAGS_check_nan_inf)")
        if flag("FLAGS_check_nan_inf_level") >= 1:
            import warnings
            warnings.warn(msg)
        else:
            raise FloatingPointError(msg)


def _next_rng_key(scope: Scope, seed: int):
    st = scope.find("__rng_state__")
    if st is None:
        st = jax.random.key(seed or 0)
    st, sub = jax.random.split(st)
    scope.set("__rng_state__", st)
    return sub
