"""Bucketed gradient collectives + ZeRO-1/2/3 sharded training (dp axis).

Reference counterparts: the fuse-all-reduce pass family —
`fuse_all_reduce_op_pass.cc:29` + `coalesce_grad_tensor_pass.cc` (grouping the
per-parameter gradient all-reduces into a few flat fused buffers, knob
`fuse_grad_size_in_mb`) and the dygraph `_coalesce_tensors` path
(`dygraph/parallel.py:229`); plus the sharding meta-optimizer's staged
partitioning (ZeRO): stage 1 optimizer state, stage 2 gradients, stage 3
parameters.

TPU-native formulation, in three layers:

1. **Program pass** (`apply_grad_bucketing`, run by
   `fleet.DistributedOptimizer.minimize`): groups the per-parameter gradient
   vars into dtype-homogeneous flat buckets of at most `fuse_grad_size_in_mb`,
   ORDERED BY GRADIENT-PRODUCTION ORDER (the backward op schedule), and
   places each bucket's sync/update op at the earliest dataflow-safe
   position — immediately after the last op producing any of the bucket's
   gradients — so XLA can overlap bucket i's collective with the backward
   compute still producing bucket i+1's gradients (the DDP bucket pipeline;
   scripts/collective_audit.py proves the interleaving structurally).

   * stage 0: per-bucket `__bucket_sync__` (grouped AR) only.
   * stage 1 (`sharding_stage=1` / `FLAGS_zero_stage=1`): each supported
     bucket's optimizer state moves into flat `[padded]` vars sharded over
     dp and its per-param update ops collapse into ONE `__zero_update__`
     (reduce_scatter -> shard-local update -> all_gather of params).
   * stage 2: the averaged gradient SHARD additionally becomes resident
     state — a flat `[padded]` bucket buffer sharded over dp written every
     step (`FlatGradOut`; the reference coalesce_grad_tensor fused-grad
     buffer, sharded). Gradients are never all-gathered anywhere, so
     gradient bytes/device divide by dp (asserted structurally via
     `compiled_memory_analysis`).
   * stage 3: parameter STORAGE moves into flat `[padded]` buckets sharded
     over dp. A per-bucket `__zero_gather__` op, placed right before the
     bucket's first forward use, all_gathers + unpacks the shard on demand;
     `__zero_update__` updates the param shard in place and never
     all_gathers it back. `@LAYERS` stacked scan params get the finer
     treatment: their storage becomes `[L, padded]` sharded on the trailing
     axis and the `__layer_scan__` body all_gathers ONE layer slice per
     scan iteration (discarded after use), with jax.vjp transposing the
     gather into a per-iteration psum_scatter — gradients for stacked
     params arrive pre-reduce-scattered.

2. **Op lowerings**: `__bucket_sync__` lowers to ONE pmean per bucket in
   manual-dp mode and to the identity otherwise. `__zero_update__` lowers
   reduce_scatter -> shard-local elementwise update (reusing the registered
   sgd/momentum/adam/adamw rule on the flat shard) -> all_gather of params
   at stages 1-2, no gather at stage 3; outside manual mode it runs the
   full-width flat update (GSPMD shards the arithmetic from the flat vars'
   dp specs). `__zero_gather__`/`__zero_pack__` convert between flat
   sharded storage and per-param views.

3. **Manual-dp runner** (`plan_manual_dp` + `build_manual_jit`, hooked from
   `framework/executor.py _CompiledBlock`): on a dp-pure mesh the whole
   step runs under `shard_map` over dp. Structural obstacles (cross-batch
   ops, SelectedRows grads, microbatch programs, indivisible batches,
   mixed meshes) fall back to the GSPMD path untouched, each counted under
   `executor.zero_manual_fallbacks.<cause>` (monitor) so a silent GSPMD
   fallback is diagnosable from stats alone.

Semantics under manual dp mirror the reference's GradAllReduce
(`transpiler/collective.py:178`: scale 1/nranks + allreduce-sum): gradients
are AVERAGED over replicas, which equals the GSPMD global-batch gradient for
mean-reduced losses (every model in models/). Scalar fetches return the
replica mean; batch-leading fetches concatenate shards in global batch order.
Random ops draw the SAME key on every replica.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..framework.program import OpRole, Operator, Program
from ..ops import registry
from ..ops.registry import register

# Padding multiple for flat ZeRO buckets: the flat state shape must not
# depend on the mesh (the same program compiles under dp=1..N), so every
# bucket pads its total element count to a multiple that any power-of-two
# dp up to 64 divides.
PAD_MULTIPLE = 64

# Update op types the flat-shard ZeRO update supports: exactly the
# ELEMENTWISE rules, for which updating the flat concatenation shard-locally
# is bit-identical to updating each parameter in full. (lamb/lars need
# per-parameter norms — their params stay on per-param update ops and only
# get the bucketed gradient sync.)
_UPDATE_STATE_SLOTS: Dict[str, Dict[str, tuple]] = {
    "sgd": {},
    "momentum": {"velocity": ("Velocity", "VelocityOut")},
    "adam": {"moment1": ("Moment1", "Moment1Out"),
             "moment2": ("Moment2", "Moment2Out")},
    "adamw": {"moment1": ("Moment1", "Moment1Out"),
              "moment2": ("Moment2", "Moment2Out")},
}
# extra replicated [1]-inputs forwarded verbatim to the inner lowering
_UPDATE_EXTRA_SLOTS = {
    "sgd": (), "momentum": (),
    "adam": ("Beta1Pow", "Beta2Pow"), "adamw": ("Beta1Pow", "Beta2Pow"),
}

# Ops whose semantics couple examples ACROSS the batch beyond a trailing
# mean-reduced loss: under GSPMD they see the global batch (sync-BN by
# construction); a manual-dp shard would silently compute LOCAL statistics,
# so their presence disables the manual path entirely. switch_moe belongs
# here too: expert capacity is FCFS over the token axis and the aux
# balancing loss averages routing stats over it, so a per-shard run drops
# different tokens and reports different aux than the global batch
# (tests/test_moe.py ep-sharded parity pins this).
#
# THE table lives on the op specs (analysis/op_specs.py `cross_batch`
# flag, read here via `cross_batch_ops()`): the static sharding lint
# (analysis/sharding.py) and this runtime decline consume the same rows,
# so a build-time "manual_dp_fallback" warning and the runtime
# `zero_manual_fallbacks.<cause>` counter can never drift apart. Loaded
# lazily — analysis imports parallel.zero for the update-rule table.


def _cross_batch_ops() -> frozenset:
    from ..analysis.op_specs import cross_batch_ops
    return cross_batch_ops()


def count_fallback(cause: str) -> None:
    """Per-cause manual-dp fallback accounting (monitor): the total under
    `executor.zero_manual_fallbacks` plus a `.<cause>` breakdown — a silent
    fallback to GSPMD is diagnosable from monitor stats alone. Causes:
    mixed_mesh, batch_norm, cross_batch (switch_moe: FCFS capacity + aux
    stats are global-batch quantities), selected_rows, pipeline,
    grad_merge, localsgd, ps_hooks, indivisible_batch,
    indivisible_padding, bucketing_disabled, plan_failure,
    unsupported_rule."""
    from .. import monitor
    from ..observability import trace as _trace
    monitor.stat_add("executor.zero_manual_fallbacks")
    monitor.stat_add(f"executor.zero_manual_fallbacks.{cause}")
    # a timeline marker too: a flight-recorder dump shows WHEN the manual
    # path bailed relative to the step windows, not just that it did
    _trace.instant("zero_manual_fallback", args={"cause": cause},
                   cat="parallel")


def _apply_update_rule(ctx, op_type: str, inner_ins, update_attrs):
    """The ONE funnel for the shard-local parameter update (both the flat
    and the @LAYERS-stacked lowerings route through here): dispatch to
    the fused Pallas bucket kernel (ops/pallas/zero_update.py, one HBM
    pass per bucket) when PADDLE_TPU_PALLAS_OPT / FLAGS_pallas_opt is on
    and the op has a fused body, else the registry rule. The two are
    bit-identical (tests/test_pallas_kernels.py), so flipping the toggle
    mid-training is checkpoint-portable in both directions."""
    from ..ops.pallas import zero_update as _zk
    if _zk.opt_kernel_enabled() and _zk.supports(op_type, inner_ins):
        from .. import monitor
        monitor.stat_add("executor.pallas_opt_fused")
        return _zk.fused_flat_update(op_type, inner_ins, update_attrs)
    return registry.get(op_type).lower(ctx, inner_ins, update_attrs)


# ---------------------------------------------------------------------------
# manual-mode trace context (set by the shard_map body; read by lowerings)
# ---------------------------------------------------------------------------

_manual_dp: List[tuple] = []   # stack of (axis_name, dp_size)


class _manual_ctx:
    def __init__(self, axis: str, dp: int):
        self._entry = (axis, int(dp))

    def __enter__(self):
        _manual_dp.append(self._entry)
        return self

    def __exit__(self, *exc):
        _manual_dp.pop()
        return False


def current_manual_dp() -> Optional[tuple]:
    """(axis_name, dp) while tracing inside the manual-dp shard_map body."""
    return _manual_dp[-1] if _manual_dp else None


# ---------------------------------------------------------------------------
# op lowerings
# ---------------------------------------------------------------------------

def _infer_noop(block, op):
    block.program.bump_version()


@register("__bucket_sync__", infer=_infer_noop,
          nondiff_slots=("X",), stateful_outputs=("Out",))
def _lower_bucket_sync(ctx, ins, attrs):
    """One grouped gradient sync per bucket: flatten → concat → pmean over
    the dp axis → split back. Identity outside manual-dp mode (GSPMD/single
    device gradients are already globally summed)."""
    import jax
    import jax.numpy as jnp

    grads = ins["X"]
    manual = current_manual_dp()
    if manual is None:
        return {"Out": list(grads)}
    axis, dp = manual
    dt = jnp.dtype(attrs["dtype"])
    flat = jnp.concatenate([jnp.reshape(g, (-1,)).astype(dt) for g in grads])
    # reference GradAllReduce semantics: allreduce-sum + 1/nranks scale
    flat = jax.lax.psum(flat, axis) * np.asarray(1.0 / dp, dt)
    outs, off = [], 0
    for g, size, shape in zip(grads, attrs["sizes"], attrs["shapes"]):
        piece = jax.lax.slice(flat, (off,), (off + size,))
        outs.append(jnp.reshape(piece, tuple(shape)).astype(g.dtype))
        off += size
    return {"Out": outs}


@register("__zero_pack__", infer=_infer_noop, nondiff_slots=("X",),
          stateful_outputs=("Out",))
def _lower_zero_pack(ctx, ins, attrs):
    """Pack per-param values into the flat [padded] (or stacked [L, padded])
    bucket layout — the startup-program side of ZeRO-3 parameter storage
    (the layer_scan `stack` op pattern, flattened)."""
    import jax.numpy as jnp

    vals = ins["X"]
    dt = jnp.dtype(attrs["dtype"])
    padded = int(attrs["padded"])
    if attrs.get("layout") == "stacked":
        v = vals[0]
        flat = jnp.reshape(v, (v.shape[0], -1)).astype(dt)
        if padded > flat.shape[1]:
            flat = jnp.concatenate(
                [flat, jnp.zeros((flat.shape[0], padded - flat.shape[1]),
                                 dt)], axis=1)
        return {"Out": [flat]}
    flat = jnp.concatenate([jnp.reshape(v, (-1,)).astype(dt) for v in vals])
    if padded > flat.shape[0]:
        flat = jnp.concatenate([flat, jnp.zeros((padded - flat.shape[0],),
                                                dt)])
    return {"Out": [flat]}


@register("__zero_gather__", infer=_infer_noop, nondiff_slots=("FlatParam",))
def _lower_zero_gather(ctx, ins, attrs):
    """ZeRO-3 on-demand parameter materialization: all_gather the bucket's
    flat dp shard (manual mode only — outside it the full array is already
    logical-width and GSPMD inserts any collective itself) and unpack into
    the per-param views the forward ops read. Placed right before the
    bucket's first use, so XLA overlaps the gather with preceding compute;
    the gathered values are temporaries, freed after their last use."""
    import jax
    import jax.numpy as jnp

    flat = ins["FlatParam"][0]
    padded = int(attrs["padded"])
    manual = current_manual_dp()
    if manual is not None and flat.shape[0] != padded:
        flat = jax.lax.all_gather(flat, manual[0], tiled=True)
    outs, off = [], 0
    for size, shape, dt in zip(attrs["sizes"], attrs["shapes"],
                               attrs["dtypes"]):
        piece = jax.lax.slice(flat, (off,), (off + size,))
        outs.append(jnp.reshape(piece, tuple(shape)).astype(jnp.dtype(dt)))
        off += size
    return {"Out": outs}


@register("__zero_update__", infer=_infer_noop,
          nondiff_slots=("Param", "Grad", "LearningRate", "Beta1Pow",
                         "Beta2Pow", "FlatState", "FlatParam"),
          stateful_outputs=("ParamOut", "FlatStateOut", "FlatParamOut",
                            "FlatGradOut"))
def _lower_zero_update(ctx, ins, attrs):
    """Staged ZeRO bucket update. Manual-dp mode: reduce_scatter the
    bucket's gradients (or slice pre-synced ones), run the registered
    elementwise update rule on the rank-local flat shard against the flat
    sharded optimizer state, then all_gather the updated parameters
    (stages 1-2) or keep the param shard resident (stage 3 — the next
    step's `__zero_gather__` rematerializes). Stage >= 2 additionally
    emits the averaged gradient shard as resident state (`FlatGradOut`).
    Outside manual mode the same math runs at full bucket width — with the
    flat vars carrying dp PartitionSpecs, GSPMD shards the arithmetic and
    inserts collectives itself, so the ~dp x memory savings survive mixed
    (dp×tp) meshes the manual path declines."""
    import jax
    import jax.numpy as jnp

    if attrs.get("layout") == "stacked":
        return _zero_update_stacked(ctx, ins, attrs)

    op_type = attrs["update_op"]
    stage = int(attrs.get("stage", 1))
    sizes = list(attrs["sizes"])
    shapes = [tuple(s) for s in attrs["shapes"]]
    padded = int(attrs["padded"])
    kinds = list(attrs["state_kinds"])
    dt = jnp.dtype(attrs["dtype"])
    grads = ins["Grad"]
    state_vals = list(ins["FlatState"])
    total = sum(sizes)

    def flat_concat(vals):
        flat = jnp.concatenate([jnp.reshape(v, (-1,)).astype(dt)
                                for v in vals])
        if padded > total:
            flat = jnp.concatenate(
                [flat, jnp.zeros((padded - total,), dt)])
        return flat

    flat_g = flat_concat(grads)
    manual = current_manual_dp()
    if stage >= 3:
        flat_p = ins["FlatParam"][0]
        # trust the actual storage width: the plan may have declined the
        # sharding (indivisible dp) even though we are in manual mode
        shard_mode = manual is not None and flat_p.shape[0] != padded
    else:
        params = ins["Param"]
        flat_p = flat_concat(params)
        shard_mode = (manual is not None and manual[1] > 1
                      and padded % manual[1] == 0)

    if shard_mode:
        axis, dp = manual
        shard = (flat_p.shape[0] if stage >= 3 else
                 (state_vals[0].shape[0] if state_vals
                  else padded // dp))
        scale = np.asarray(1.0 / dp, dt)
        idx = jax.lax.axis_index(axis)
        if attrs.get("pre_synced"):
            # gradients already bucket-synced (clip/regularization ops sit
            # between sync and update): just take this rank's slice
            g_shard = jax.lax.dynamic_slice(flat_g, (idx * shard,), (shard,))
        else:
            # the comm-optimal path: reduce_scatter INSTEAD of all-reduce —
            # each rank receives only the bucket shard it will update
            g_shard = jax.lax.psum_scatter(flat_g, axis,
                                           scatter_dimension=0,
                                           tiled=True) * scale
        p_shard = flat_p if stage >= 3 else \
            jax.lax.dynamic_slice(flat_p, (idx * shard,), (shard,))
    else:
        # full-width update: single device, GSPMD fallback, or a dp the
        # padding does not divide (state then stays replicated). In the
        # last case the gradients are still LOCAL (the pass routed this
        # bucket around __bucket_sync__) — they MUST be averaged here or
        # the replicas silently train on divergent updates.
        if manual is not None and not attrs.get("pre_synced"):
            axis, dp = manual
            flat_g = jax.lax.psum(flat_g, axis) * np.asarray(1.0 / dp, dt)
        g_shard, p_shard = flat_g, flat_p

    inner_ins = {"Param": [p_shard], "Grad": [g_shard],
                 "LearningRate": ins["LearningRate"]}
    for extra in _UPDATE_EXTRA_SLOTS[op_type]:
        inner_ins[extra] = ins[extra]
    slot_map = _UPDATE_STATE_SLOTS[op_type]
    for kind, val in zip(kinds, state_vals):
        inner_ins[slot_map[kind][0]] = [val]
    res = _apply_update_rule(ctx, op_type, inner_ins,
                             dict(attrs["update_attrs"]))

    p_new = res["ParamOut"][0]
    outs = {}
    if stage >= 3:
        # ZeRO-3: the updated param SHARD is the resident state — no
        # all_gather here; the next step's __zero_gather__ rematerializes
        outs["FlatParamOut"] = [p_new]
    else:
        if p_new.shape[0] != padded:   # manual: reassemble the full params
            p_new = jax.lax.all_gather(p_new, manual[0], tiled=True)
        po, off = [], 0
        for size, shape, p in zip(sizes, shapes, params):
            piece = jax.lax.slice(p_new, (off,), (off + size,))
            po.append(jnp.reshape(piece, shape).astype(p.dtype))
            off += size
        outs["ParamOut"] = po
    outs["FlatStateOut"] = [res[slot_map[kind][1]][0] for kind in kinds]
    if stage >= 2:
        # ZeRO-2: the AVERAGED gradient shard stays resident (the
        # reference's fused-grad coalesce buffer, sharded over dp) — never
        # all-gathered, so gradient state bytes/device divide by dp
        outs["FlatGradOut"] = [g_shard.astype(dt)]
    return outs


def _zero_update_stacked(ctx, ins, attrs):
    """ZeRO-3 update for an `@LAYERS` stacked scan param: storage is
    [L, padded] sharded on the trailing axis; the gradient arrives from the
    `__layer_scan__` vjp already reduce-scattered per iteration (the
    transpose of the per-iteration all_gather), so the update is purely
    local: scale 1/dp + elementwise rule on the [L, padded/dp] shard."""
    import jax
    import jax.numpy as jnp

    op_type = attrs["update_op"]
    padded = int(attrs["padded"])
    kinds = list(attrs["state_kinds"])
    dt = jnp.dtype(attrs["dtype"])
    p = ins["FlatParam"][0]
    g = ins["Grad"][0]
    manual = current_manual_dp()
    if manual is not None:
        axis, dp = manual
        if g.shape[-1] == padded and p.shape[-1] == padded:
            # full-width fallback (dp does not divide the padding): grads
            # are LOCAL — average them
            g = jax.lax.psum(g, axis)
        g = g * np.asarray(1.0 / dp, g.dtype)
    g = jnp.reshape(g, p.shape).astype(dt)

    inner_ins = {"Param": [p], "Grad": [g],
                 "LearningRate": ins["LearningRate"]}
    for extra in _UPDATE_EXTRA_SLOTS[op_type]:
        inner_ins[extra] = ins[extra]
    slot_map = _UPDATE_STATE_SLOTS[op_type]
    for kind, val in zip(kinds, ins["FlatState"]):
        inner_ins[slot_map[kind][0]] = [val]
    res = _apply_update_rule(ctx, op_type, inner_ins,
                             dict(attrs["update_attrs"]))
    outs = {"FlatParamOut": [res["ParamOut"][0]],
            "FlatStateOut": [res[slot_map[kind][1]][0] for kind in kinds]}
    if int(attrs.get("stage", 3)) >= 2:
        outs["FlatGradOut"] = [g]
    return outs


# ---------------------------------------------------------------------------
# the program pass
# ---------------------------------------------------------------------------

def _plan_buckets(items: Sequence[tuple], bucket_bytes: int,
                  key_fn) -> List[List[tuple]]:
    """Greedy in-order grouping into buckets of <= bucket_bytes, split on a
    change of key (dtype / update-op signature) — the reference
    coalesce_grad_tensor grouping."""
    buckets: List[List[tuple]] = []
    cur: List[tuple] = []
    cur_key, cur_bytes = None, 0
    for it in items:
        k = key_fn(it)
        nb = it[-1]          # trailing element = nbytes
        if cur and (k != cur_key or cur_bytes + nb > bucket_bytes):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur_key = k
        cur.append(it)
        cur_bytes += nb
    if cur:
        buckets.append(cur)
    return buckets


def _var_nbytes(var) -> int:
    n = 1
    for d in var.shape:
        n *= max(int(d), 1)
    try:
        item = np.dtype(var.dtype).itemsize
    except TypeError:
        item = 4
    return n * item


def _numel(var) -> int:
    n = 1
    for d in var.shape:
        n *= max(int(d), 1)
    return n


def _pad64(n: int) -> int:
    return int(math.ceil(n / PAD_MULTIPLE) * PAD_MULTIPLE)


def apply_grad_bucketing(program: Program, startup_program: Program,
                         params_grads, bucket_bytes: int,
                         stage: int = 0) -> Optional[dict]:
    """Rewrite `program` in place; returns the bucket metadata (also stored
    as `program._grad_buckets`) or None when nothing was bucketable.

    stage=0: per-bucket `__bucket_sync__` ops (grouped AR), each placed at
    its own bucket's backward-ready point (the overlap pipeline).
    stage=1: additionally move each supported bucket's optimizer state into
    flat `[padded]` vars (startup-initialized, dp-sharded via
    `program._zero_state_specs`) and replace its per-param update ops with
    one `__zero_update__`; unsupported update rules keep their per-param
    ops and degrade to stage-0 sync.
    stage=2: the averaged gradient shard becomes resident flat state too.
    stage=3: parameter storage moves into flat dp-sharded buckets with
    on-demand `__zero_gather__` (per layer-scan iteration for `@LAYERS`
    stacked params).
    """
    from ..analysis.passes import checked_pass
    with checked_pass("grad_bucketing", program,
                      startup_program=startup_program):
        return _apply_grad_bucketing(program, startup_program,
                                     params_grads, bucket_bytes,
                                     stage=stage)


def _apply_grad_bucketing(program: Program, startup_program: Program,
                          params_grads, bucket_bytes: int,
                          stage: int = 0) -> Optional[dict]:
    if getattr(program, "_grad_bucketing_unsafe", False):
        return None   # gated optimizer sections (gradient merge) opt out
    block = program.global_block()
    dense_pgs = []
    for p, g in params_grads or []:
        gv = block.find_var_recursive(g.name if hasattr(g, "name") else g)
        pv = block.find_var_recursive(p.name if hasattr(p, "name") else p)
        if gv is None or pv is None or \
                getattr(gv, "_is_selected_rows", False):
            continue
        dense_pgs.append((pv, gv))
    if not dense_pgs:
        return None

    # The backward op schedule: index of the LAST op producing each grad.
    # Buckets form in GRADIENT-PRODUCTION ORDER (reverse forward order) so
    # that each bucket's collective can start while later buckets' grads
    # are still being computed — the DDP bucket pipeline.
    prod_idx: Dict[str, int] = {}
    for i, op in enumerate(block.ops):
        for n in op.output_names():
            if n != "@EMPTY@":
                prod_idx[n] = i
    dense_pgs.sort(key=lambda pg: prod_idx.get(pg[1].name, 1 << 30))

    # grad -> the single per-param update op consuming it (stage 1 targets)
    update_ops: Dict[str, Operator] = {}
    grad_consumers: Dict[str, int] = {g.name: 0 for _, g in dense_pgs}
    for op in block.ops:
        for n in op.input_names():
            if n in grad_consumers:
                grad_consumers[n] += 1
        if op.type in _UPDATE_STATE_SLOTS \
                and op.attrs.get("op_role", 0) == OpRole.Optimize:
            gname = (op.inputs.get("Grad") or [None])[0]
            pname = (op.inputs.get("Param") or [None])[0]
            pouts = op.outputs.get("ParamOut") or [None]
            if gname and pname and pouts[0] == pname:
                update_ops[pname] = op

    zero_meta: List[dict] = []
    zero_removed: List[Operator] = []

    # stage 3, rolled programs: @LAYERS stacked scan params route to the
    # per-scan-iteration gather path (their own [L, padded] buckets)
    stacked_handled: set = set()
    if stage >= 3:
        stacked_handled = _plan_stacked_stage3(
            program, startup_program, block, dense_pgs, update_ops,
            grad_consumers, zero_meta, zero_removed)
        if stacked_handled:
            dense_pgs = [pg for pg in dense_pgs
                         if pg[0].name not in stacked_handled]

    if stage >= 1:
        # group params whose update op shares (type, attrs, lr, pows, dtype)
        def upd_key(item):
            pv, gv = item[0], item[1]
            op = update_ops.get(pv.name)
            if op is None:
                return None
            at = tuple(sorted((k, repr(v)) for k, v in op.attrs.items()
                              if k != "op_role"))
            extras = tuple(tuple(op.inputs.get(s, ()))
                           for s in _UPDATE_EXTRA_SLOTS[op.type])
            return (op.type, at, str(pv.dtype),
                    tuple(op.inputs.get("LearningRate", ())), extras)

        items = [(pv, gv, _var_nbytes(pv)) for pv, gv in dense_pgs]
        for group in _plan_buckets(items, bucket_bytes, upd_key):
            if upd_key(group[0]) is None:
                count_fallback("unsupported_rule")
                continue   # unsupported rule: stage-0 sync only (below)
            zero_meta.append(_build_zero_bucket(
                program, startup_program, block,
                [(pv, gv) for pv, gv, _ in group],
                update_ops, len(zero_meta), grad_consumers, zero_removed,
                stage=stage))

    # stage>=1 RS-mode buckets consume UNSYNCED grads (their __zero_update__
    # reduce-scatters them itself); every other dense grad gets a grouped
    # sync op at the backward->optimize boundary
    sync_meta: List[dict] = []
    rs_grads = {g for b in zero_meta if not b["pre_synced"]
                for g in b["grads"]}
    synced_grads = [(pv, gv) for pv, gv in dense_pgs
                    if gv.name not in rs_grads]
    if synced_grads:
        items = [(pv, gv, _var_nbytes(gv)) for pv, gv in synced_grads]
        for group in _plan_buckets(items, bucket_bytes,
                                   lambda it: str(it[1].dtype)):
            gvars = [gv for _, gv, _ in group]
            sync_meta.append({
                "grads": [g.name for g in gvars],
                "sizes": [_numel(g) for g in gvars],
                "shapes": [list(g.shape) for g in gvars],
                "dtype": str(np.dtype(gvars[0].dtype)),
            })
        # insert all sync ops right after the last op writing any of the
        # bucketed grads (the backward->optimize boundary); the scheduling
        # pass below then sinks each one to ITS bucket's ready point
        sync_names = {g for m in sync_meta for g in m["grads"]}
        last_w = max((i for i, op in enumerate(block.ops)
                      if sync_names & set(op.output_names())), default=None)
        if last_w is None:
            return None
        at = last_w + 1
        sync_ops = []
        for m in sync_meta:
            sync_ops.append(block._insert_op(
                at, "__bucket_sync__",
                inputs={"X": list(m["grads"])},
                outputs={"Out": list(m["grads"])},
                attrs={"sizes": m["sizes"], "shapes": m["shapes"],
                       "dtype": m["dtype"], "op_role": OpRole.Optimize}))
            at += 1
    else:
        sync_ops = []

    # stage 3: per-bucket on-demand gathers, placed right before the
    # bucket's FIRST forward use (latest-possible materialization)
    if stage >= 3:
        _insert_zero_gathers(block, zero_meta)

    # The overlap pipeline: sink every bucket sync/update op from the
    # boundary to the earliest dataflow-safe slot — right after the last
    # op producing any of ITS gradients (and any other input), so the
    # collectives interleave with the remaining backward compute instead
    # of forming one wall after it.
    from .transforms import sink_op_to_producers
    from ..analysis.passes import verify_passes_enabled
    bucket_ops = sync_ops + [op for op in block.ops
                             if op.type == "__zero_update__"]
    before_motion = list(block.ops) if verify_passes_enabled() else None
    for op in bucket_ops:
        sink_op_to_producers(block, op)
    if before_motion is not None:
        # code motion gets the stronger invariant on top of the structural
        # verifier: the sink may only REORDER ops, never swap a dependent
        # pair (write->read / read->write / write->write on any var)
        from ..analysis.collectives import dataflow_preserved
        from ..analysis.passes import PassVerificationError
        motion_errs = dataflow_preserved(before_motion, block.ops,
                                         pass_name="sink_op_to_producers")
        if motion_errs:
            raise PassVerificationError("sink_op_to_producers", motion_errs)

    meta = {"stage": int(stage), "bucket_bytes": int(bucket_bytes),
            "sync_buckets": sync_meta, "zero_buckets": zero_meta}
    program._grad_buckets = meta
    program._zero_buckets = zero_meta
    specs: Dict[str, tuple] = {}
    for b in zero_meta:
        spec = (None, "dp") if b.get("layout") == "stacked" else ("dp",)
        for n in b["flat"].values():
            specs[n] = spec
        if b.get("flat_grad"):
            specs[b["flat_grad"]] = spec
        if b.get("flat_param"):
            specs[b["flat_param"]] = spec
    program._zero_state_specs = specs
    program.bump_version()
    return meta


def _drop_startup_inits(startup_block, names) -> None:
    """Remove `names`' init ops + vars from the startup program (their
    replicated full-width values are exactly the memory ZeRO avoids)."""
    doomed = set(names)
    startup_block.ops = [op for op in startup_block.ops
                         if not (set(op.output_names()) & doomed)]
    for n in doomed:
        startup_block.vars.pop(n, None)


def _startup_flat_zeros(startup_block, name, shape, dtype) -> None:
    startup_block.create_var(name=name, shape=tuple(shape), dtype=dtype,
                             persistable=True, stop_gradient=True)
    startup_block.append_op(
        "fill_constant", inputs={},
        outputs={"Out": [name]},
        attrs={"shape": list(shape), "dtype": dtype, "value": 0.0})


def _build_zero_bucket(program, startup_program, block, group, update_ops,
                       idx, grad_consumers, removed_acc, stage=1) -> dict:
    """Replace `group`'s per-param update ops with one __zero_update__ over
    flat bucket state; returns the bucket's metadata record."""
    from ..framework import unique_name

    ops = [update_ops[pv.name] for pv, _ in group]
    op0 = ops[0]
    params = [pv for pv, _ in group]
    upd_grads = [op.inputs["Grad"][0] for op in ops]
    sizes = [_numel(pv) for pv in params]
    total = sum(sizes)
    padded = _pad64(total)
    dtype = str(np.dtype(params[0].dtype))
    kinds = sorted(_UPDATE_STATE_SLOTS[op0.type])
    label = f"zero{stage}_b{idx}"

    # the update ops consume the raw grads directly (and nothing else reads
    # them): reduce_scatter replaces the all-reduce entirely. Any
    # intervening clip/regularization op keeps the bucket in pre-synced
    # slice mode instead.
    raw_direct = all(
        g == pv.grad_name() and grad_consumers.get(g, 0) == 1
        for (pv, _), g in zip(group, upd_grads))

    per_param_state = {}
    flat = {}
    startup_block = startup_program.global_block() \
        if startup_program is not None else None
    for kind in kinds:
        in_slot = _UPDATE_STATE_SLOTS[op0.type][kind][0]
        per_param = {pv.name: op.inputs[in_slot][0]
                     for (pv, _), op in zip(group, ops)}
        fname = unique_name.generate(f"{label}_{kind}")
        fv = block.create_var(name=fname, shape=(padded,), dtype=dtype,
                              persistable=True, stop_gradient=True)
        fv.persistable = True
        flat[kind] = fname
        for pn, mn in per_param.items():
            per_param_state.setdefault(pn, {})[kind] = mn
        # drop the per-param accumulators: main-program vars and their
        # startup init ops (a full replica of them is exactly the memory
        # ZeRO-1 exists to not allocate)
        for mn in per_param.values():
            block.vars.pop(mn, None)
        if startup_block is not None:
            _drop_startup_inits(startup_block, set(per_param.values()))
            _startup_flat_zeros(startup_block, fname, (padded,), dtype)

    flat_grad = flat_param = None
    if stage >= 2:
        # ZeRO-2: a resident flat buffer for the bucket's AVERAGED gradient
        # shard — the reference's coalesced fused-grad buffer, dp-sharded.
        # Written every step by __zero_update__, never all-gathered.
        flat_grad = unique_name.generate(f"{label}_gradbuf")
        block.create_var(name=flat_grad, shape=(padded,), dtype=dtype,
                         persistable=True, stop_gradient=True)
        if startup_block is not None:
            _startup_flat_zeros(startup_block, flat_grad, (padded,), dtype)
    if stage >= 3:
        # ZeRO-3: parameter STORAGE moves into the flat dp-sharded bucket;
        # the per-param vars demote to transients materialized on demand by
        # __zero_gather__ (so they stop being saved/loaded/donated state)
        flat_param = unique_name.generate(f"zero3_b{idx}_param")
        block.create_var(name=flat_param, shape=(padded,), dtype=dtype,
                         persistable=True, stop_gradient=True)
        for pv in params:
            pv.persistable = False
        if startup_block is not None:
            pnames = [pv.name for pv in params]
            if all(n in startup_block.vars for n in pnames):
                for n in pnames:
                    startup_block.vars[n].persistable = False
                startup_block.create_var(
                    name=flat_param, shape=(padded,), dtype=dtype,
                    persistable=True, stop_gradient=True)
                startup_block.append_op(
                    "__zero_pack__", inputs={"X": pnames},
                    outputs={"Out": [flat_param]},
                    attrs={"sizes": sizes, "padded": padded,
                           "dtype": dtype})

    extra_inputs = {s: list(op0.inputs.get(s, ()))
                    for s in _UPDATE_EXTRA_SLOTS[op0.type]}
    update_attrs = {k: v for k, v in op0.attrs.items() if k != "op_role"}

    pos = min(block.ops.index(op) for op in ops)
    for op in ops:
        block.ops.remove(op)
    removed_acc.extend(ops)
    inputs = {"Grad": list(upd_grads),
              "LearningRate": list(op0.inputs.get("LearningRate", ())),
              "FlatState": [flat[k] for k in kinds]}
    outputs = {"FlatStateOut": [flat[k] for k in kinds]}
    if stage >= 3:
        inputs["FlatParam"] = [flat_param]
        outputs["FlatParamOut"] = [flat_param]
    else:
        inputs["Param"] = [pv.name for pv in params]
        outputs["ParamOut"] = [pv.name for pv in params]
    if stage >= 2:
        outputs["FlatGradOut"] = [flat_grad]
    inputs.update(extra_inputs)
    block.ops.insert(pos, Operator(
        block, "__zero_update__", inputs, outputs,
        {"update_op": op0.type, "update_attrs": update_attrs,
         "sizes": sizes, "shapes": [list(pv.shape) for pv in params],
         "padded": padded, "dtype": dtype, "state_kinds": kinds,
         "pre_synced": not raw_direct, "stage": int(stage),
         "layout": "flat", "op_role": OpRole.Optimize}))

    return {"op_type": op0.type, "params": [pv.name for pv in params],
            "grads": list(upd_grads), "sizes": sizes,
            "shapes": [list(pv.shape) for pv in params],
            "padded": padded, "flat_numel": padded, "dtype": dtype,
            "flat": flat, "per_param_state": per_param_state,
            "pre_synced": not raw_direct, "stage": int(stage),
            "layout": "flat", "flat_grad": flat_grad,
            "flat_param": flat_param}


def _insert_zero_gathers(block, zero_meta) -> None:
    """Insert one `__zero_gather__` per stage-3 flat bucket, right before
    the FIRST op reading any of the bucket's params — the latest position
    that keeps dataflow valid, so gathered full-width params live as
    briefly as possible."""
    plans = []
    for b in zero_meta:
        if b.get("layout") != "flat" or not b.get("flat_param"):
            continue
        pset = set(b["params"])
        first = next((i for i, op in enumerate(block.ops)
                      if pset & set(op.input_names())), len(block.ops))
        plans.append((first, b))
    # insert from the back so earlier indices stay valid
    for first, b in sorted(plans, key=lambda t: -t[0]):
        dtypes = []
        for n in b["params"]:
            v = block.find_var_recursive(n)
            dtypes.append(str(np.dtype(v.dtype)) if v is not None
                          else b["dtype"])
        block._insert_op(
            first, "__zero_gather__",
            inputs={"FlatParam": [b["flat_param"]]},
            outputs={"Out": list(b["params"])},
            attrs={"sizes": b["sizes"], "shapes": b["shapes"],
                   "dtypes": dtypes, "padded": b["padded"],
                   "op_role": OpRole.Forward})


def _plan_stacked_stage3(program, startup_program, block, dense_pgs,
                         update_ops, grad_consumers, zero_meta,
                         removed_acc) -> set:
    """Route `@LAYERS` stacked scan params to the per-scan-iteration gather
    path: storage [L, padded] sharded on the trailing axis, one all_gather
    per scan iteration inside the `__layer_scan__` body (jax.vjp transposes
    it into a per-iteration psum_scatter, so grads arrive pre-sharded).
    Returns the param names handled here (removed from the flat path)."""
    stacks = getattr(program, "_layer_stacks", None) or {}
    if not stacks:
        return set()
    scan_ops = [op for op in block.ops if op.type == "__layer_scan__"]
    if not scan_ops:
        return set()
    vjp_ops = [op for op in block.ops
               if op.type == "__vjp__"
               and op.attrs.get("fwd_type") == "__layer_scan__"]
    handled = set()
    for pv, gv in dense_pgs:
        sname = pv.name
        if sname not in stacks:
            continue
        op = update_ops.get(sname)
        if op is None or op.type not in _UPDATE_STATE_SLOTS:
            continue
        g = op.inputs["Grad"][0]
        if g != pv.grad_name() or grad_consumers.get(g, 0) != 1:
            continue   # clip/regularized grads: flat gather-at-start path
        scan = next((s for s in scan_ops
                     if sname in s.inputs.get("Stacked", [])), None)
        vjp = next((v for v in vjp_ops
                    if sname in v.inputs.get("Stacked", [])), None)
        if scan is None or vjp is None:
            continue
        zero_meta.append(_build_zero3_stacked_bucket(
            program, startup_program, block, pv, op, scan, vjp,
            len(zero_meta), removed_acc))
        handled.add(sname)
    return handled


def _build_zero3_stacked_bucket(program, startup_program, block, pv,
                                upd_op, scan_op, vjp_op, idx,
                                removed_acc) -> dict:
    from ..framework import unique_name

    L = int(pv.shape[0])
    per_shape = tuple(int(d) for d in pv.shape[1:])
    per = 1
    for d in per_shape:
        per *= max(d, 1)
    padded = _pad64(per)
    dtype = str(np.dtype(pv.dtype))
    kinds = sorted(_UPDATE_STATE_SLOTS[upd_op.type])
    label = f"zero3_s{idx}"
    startup_block = startup_program.global_block() \
        if startup_program is not None else None

    flat = {}
    per_param_state = {}
    for kind in kinds:
        in_slot = _UPDATE_STATE_SLOTS[upd_op.type][kind][0]
        mn = upd_op.inputs[in_slot][0]
        fname = unique_name.generate(f"{label}_{kind}")
        block.create_var(name=fname, shape=(L, padded), dtype=dtype,
                         persistable=True, stop_gradient=True)
        flat[kind] = fname
        per_param_state.setdefault(pv.name, {})[kind] = mn
        block.vars.pop(mn, None)
        if startup_block is not None:
            _drop_startup_inits(startup_block, {mn})
            _startup_flat_zeros(startup_block, fname, (L, padded), dtype)

    fpname = unique_name.generate(f"{label}_param")
    block.create_var(name=fpname, shape=(L, padded), dtype=dtype,
                     persistable=True, stop_gradient=True)
    pv.persistable = False
    flat_grad = unique_name.generate(f"{label}_gradbuf")
    block.create_var(name=flat_grad, shape=(L, padded), dtype=dtype,
                     persistable=True, stop_gradient=True)
    if startup_block is not None:
        _startup_flat_zeros(startup_block, flat_grad, (L, padded), dtype)
        if pv.name in startup_block.vars:
            startup_block.vars[pv.name].persistable = False
            startup_block.create_var(name=fpname, shape=(L, padded),
                                     dtype=dtype, persistable=True,
                                     stop_gradient=True)
            startup_block.append_op(
                "__zero_pack__", inputs={"X": [pv.name]},
                outputs={"Out": [fpname]},
                attrs={"padded": padded, "dtype": dtype,
                       "layout": "stacked"})

    # rewrite the scan (and its vjp twin) to consume the flat shard and
    # gather ONE layer slice per iteration inside the body
    si = scan_op.inputs["Stacked"].index(pv.name)
    zero3 = list(scan_op.attrs.get("zero3_flat")
                 or [None] * len(scan_op.inputs["Stacked"]))
    zero3[si] = {"size": per, "shape": list(per_shape), "padded": padded}
    scan_op.inputs["Stacked"][si] = fpname
    scan_op.attrs["zero3_flat"] = zero3
    vi = vjp_op.inputs["Stacked"].index(pv.name)
    vjp_op.inputs["Stacked"][vi] = fpname
    # the vjp op re-lowers the forward from its own COPY of the attrs —
    # keep it in sync or backward would trace the un-gathered layout
    vjp_op.attrs["fwd_attrs"] = dict(vjp_op.attrs["fwd_attrs"])
    vjp_op.attrs["fwd_attrs"]["zero3_flat"] = zero3
    # the gradient now differentiates the FLAT [L, padded] input (the
    # gather sits inside the body), so the grad var's recorded metadata
    # must follow — the program verifier pins grad vars to their forward
    # input's shape/dtype (analysis/verifier.py grad_shape)
    gvar = block.find_var_recursive(pv.grad_name())
    if gvar is not None:
        gvar.shape = (L, padded)
        gvar.dtype = np.dtype(dtype)

    gname = upd_op.inputs["Grad"][0]
    pos = block.ops.index(upd_op)
    block.ops.remove(upd_op)
    removed_acc.append(upd_op)
    inputs = {"FlatParam": [fpname], "Grad": [gname],
              "LearningRate": list(upd_op.inputs.get("LearningRate", ())),
              "FlatState": [flat[k] for k in kinds]}
    for s in _UPDATE_EXTRA_SLOTS[upd_op.type]:
        inputs[s] = list(upd_op.inputs.get(s, ()))
    update_attrs = {k: v for k, v in upd_op.attrs.items() if k != "op_role"}
    block.ops.insert(pos, Operator(
        block, "__zero_update__", inputs,
        {"FlatParamOut": [fpname], "FlatStateOut": [flat[k] for k in kinds],
         "FlatGradOut": [flat_grad]},
        {"update_op": upd_op.type, "update_attrs": update_attrs,
         "sizes": [per], "shapes": [list(per_shape)], "padded": padded,
         "num_layers": L, "dtype": dtype, "state_kinds": kinds,
         "pre_synced": False, "stage": 3, "layout": "stacked",
         "op_role": OpRole.Optimize}))
    program.bump_version()

    return {"op_type": upd_op.type, "params": [pv.name], "grads": [gname],
            "sizes": [per], "shapes": [list(per_shape)], "padded": padded,
            "flat_numel": L * padded, "num_layers": L, "dtype": dtype,
            "flat": flat, "per_param_state": per_param_state,
            "pre_synced": False, "stage": 3, "layout": "stacked",
            "flat_grad": flat_grad, "flat_param": fpname,
            "stack_var": pv.name}


# ---------------------------------------------------------------------------
# checkpoint round-trip (unsharded <-> flat-bucket state)
# ---------------------------------------------------------------------------

def _unpack_flat(flat, b):
    """flat bucket array -> {per-entry-name: unsharded view}."""
    out = {}
    flat = np.asarray(flat)
    if b.get("layout") == "stacked":
        per = b["sizes"][0]
        shape = (b["num_layers"],) + tuple(b["shapes"][0])
        out[b["stack_var"]] = flat[:, :per].reshape(shape)
        return out
    flat = flat.reshape(-1)
    off = 0
    for p, size, shape in zip(b["params"], b["sizes"], b["shapes"]):
        out[p] = flat[off:off + size].reshape(tuple(shape))
        off += size
    return out


def _pack_flat(values, b, dtype):
    """per-entry unsharded arrays (in bucket order) -> flat bucket array."""
    if b.get("layout") == "stacked":
        v = np.asarray(values[0])
        L = b["num_layers"]
        flat = v.reshape(L, -1).astype(np.dtype(dtype))
        if b["padded"] > flat.shape[1]:
            flat = np.concatenate(
                [flat, np.zeros((L, b["padded"] - flat.shape[1]),
                                flat.dtype)], axis=1)
        return flat
    flat = np.concatenate([np.asarray(v).reshape(-1) for v in values]) \
        .astype(np.dtype(dtype))
    if b["padded"] > flat.shape[0]:
        flat = np.concatenate(
            [flat, np.zeros(b["padded"] - flat.shape[0], flat.dtype)])
    return flat


def adopt_unsharded_state(program, scope) -> None:
    """Scope round-trip for ZeRO programs (the `_ensure_shared_beta_pows`
    adoption pattern; `Executor._resolve_call` runs the three adoptions
    together, before it collects the state names): when every per-param
    entry of a bucket×kind is
    present in the scope — an UNSHARDED checkpoint was just loaded — pack
    them into the flat bucket var the program reads and drop the per-param
    copies. Loaded values win over a previously flat value; partial sets
    are ambiguous and adopt nothing. Only the program's own RECORDED
    per-param names are ever touched (a closed list, like the beta-pow
    adoption). Stage 3 additionally adopts the PARAMETERS themselves —
    per-param (or restacked `@LAYERS`) scope entries only exist right
    after an unsharded checkpoint load, never from training (the program
    writes only the flat storage).

    This adoption IS the elastic dp-resize resume path (train on N ranks,
    resume on M): the flat layouts are mesh-independent by construction
    ([padded-to-64] and [L, padded]), so a checkpoint written under ANY dp
    width packs into byte-identical flat arrays here, and the executor's
    in_shardings re-shard them for the restoring mesh on the first
    dispatch — or replicate them when the new width does not divide the
    padding (the full-width fallback, counted under
    `executor.zero_manual_fallbacks.indivisible_padding`)."""
    buckets = getattr(program, "_zero_buckets", None)
    if not buckets:
        return
    import jax.numpy as jnp
    gb = program.global_block()
    for b in buckets:
        stacked = b.get("layout") == "stacked"
        legacy_params = [b["stack_var"]] if stacked else b["params"]
        groups = []
        for kind, fname in b["flat"].items():
            legacy = [b["per_param_state"][p][kind] for p in legacy_params]
            if any(gb.has_var(n) for n in legacy):
                continue
            groups.append((fname, legacy))
        if b.get("flat_param"):
            # per-param PARAM scope entries appear only when an unsharded
            # checkpoint was loaded (their block vars exist but demoted to
            # non-persistable, so training never writes them back)
            groups.append((b["flat_param"], list(legacy_params)))
        for fname, legacy in groups:
            if not all(scope.has(n) for n in legacy):
                continue
            vals, ok = [], True
            want_shapes = ([(b["num_layers"],) + tuple(b["shapes"][0])]
                           if stacked else
                           [tuple(s) for s in b["shapes"]])
            for n, shape in zip(legacy, want_shapes):
                v = np.asarray(scope.find(n))
                if tuple(v.shape) != shape:
                    ok = False
                    break
                vals.append(v)
            if not ok:
                continue
            scope.set(fname, jnp.asarray(_pack_flat(vals, b, b["dtype"])))
            for n in legacy:
                scope.erase(n)


def unbucket_state_for_save(program, arrays: dict) -> dict:
    """Checkpoint PORTABILITY (io.save_persistables hook): replace each flat
    bucket entry with its per-param views, so checkpoints written under ANY
    ZeRO stage are plain unsharded checkpoints — loadable by a replicated
    program directly and by a ZeRO program via `adopt_unsharded_state`, in
    every direction. Stage-2 gradient buffers are per-step scratch and are
    dropped entirely (they are reproducible, never checkpoint state)."""
    buckets = getattr(program, "_zero_buckets", None)
    if not buckets:
        return arrays
    out = dict(arrays)
    for b in buckets:
        stacked = b.get("layout") == "stacked"
        legacy_params = [b["stack_var"]] if stacked else b["params"]
        for kind, fname in b["flat"].items():
            flat = out.pop(fname, None)
            if flat is None:
                continue
            views = _unpack_flat(flat, b)
            for p in legacy_params:
                out[b["per_param_state"][p][kind]] = views[p]
        if b.get("flat_grad"):
            out.pop(b["flat_grad"], None)
        if b.get("flat_param"):
            flat = out.pop(b["flat_param"], None)
            if flat is not None:
                out.update(_unpack_flat(flat, b))
    return out


def optimizer_state_bytes(program, dp: int = 1) -> dict:
    """Structural per-device state accounting (bench extras + the tier-1
    memory tests): flat ZeRO bucket bytes divide by dp when the padding
    does; replicated per-param accumulators count at full width on every
    device; stage >= 2 adds the resident gradient-shard bytes and stage 3
    the parameter-shard bytes. Everything derived from program metadata,
    no timing."""
    buckets = getattr(program, "_zero_buckets", None) or []
    meta = getattr(program, "_grad_buckets", None) or {}
    flat_total = grad_total = param_total = 0
    for b in buckets:
        item = np.dtype(b["dtype"]).itemsize
        numel = b.get("flat_numel", b["padded"])
        flat_total += numel * item * len(b["flat"])
        if b.get("flat_grad"):
            grad_total += numel * item
        if b.get("flat_param"):
            param_total += numel * item
    # per-param accumulators still on per-param update ops (replicated
    # programs entirely; under ZeRO the unsupported-rule leftovers)
    block = program.global_block()
    repl_total = 0
    seen = set()
    for op in block.ops:
        if op.type not in _UPDATE_STATE_SLOTS \
                or op.attrs.get("op_role", 0) != OpRole.Optimize:
            continue
        for kind, (in_slot, _out) in _UPDATE_STATE_SLOTS[op.type].items():
            for n in op.inputs.get(in_slot, ()):
                if n in seen:
                    continue
                seen.add(n)
                v = block.find_var_recursive(n)
                if v is not None:
                    repl_total += _var_nbytes(v)
    sharded = all(b["padded"] % max(dp, 1) == 0 for b in buckets)
    div = dp if (dp > 1 and sharded) else 1
    flat_per_dev = flat_total // div
    return {"flat_state_bytes_total": int(flat_total),
            "flat_state_bytes_per_device": int(flat_per_dev),
            "flat_grad_bytes_total": int(grad_total),
            "flat_grad_bytes_per_device": int(grad_total // div),
            "flat_param_bytes_total": int(param_total),
            "flat_param_bytes_per_device": int(param_total // div),
            "replicated_state_bytes": int(repl_total),
            "state_bytes_per_device": int(flat_per_dev + repl_total),
            "dp": int(dp),
            "zero_stage": int(meta.get("stage", 1)) if buckets else 0}


def _iter_op_types(program):
    """Every op type in the program, INCLUDING fused sub-graph bodies
    (__segment__/__layer_scan__ sub_ops, and the __vjp__ twins' fwd_attrs
    copies) — structural scans that gate execution paths must see through
    the fusion passes."""
    def walk(attrs):
        for od in attrs.get("sub_ops") or ():
            yield od.get("type")
            yield from walk(od.get("attrs", {}))
        fwd = attrs.get("fwd_attrs")
        if isinstance(fwd, dict):
            yield from walk(fwd)
    for b in program.blocks:
        for op in b.ops:
            yield op.type
            yield from walk(op.attrs)


# ---------------------------------------------------------------------------
# the manual-dp execution plan (hooked from executor._CompiledBlock)
# ---------------------------------------------------------------------------

class ManualDpPlan:
    __slots__ = ("axis", "dp", "mesh", "feed_specs", "state_specs",
                 "fetch_gathers", "written_specs", "local_batch")

    def __init__(self, axis, dp, mesh, feed_specs, state_specs,
                 fetch_gathers, written_specs, local_batch):
        self.axis = axis
        self.dp = dp
        self.mesh = mesh
        self.feed_specs = feed_specs
        self.state_specs = state_specs
        self.fetch_gathers = fetch_gathers
        self.written_specs = written_specs
        self.local_batch = local_batch


def spec_axes(spec) -> tuple:
    """Normalize a _zero_state_specs value ("dp" | tuple of axes/None) to
    the PartitionSpec axes tuple."""
    return (spec,) if isinstance(spec, str) else tuple(spec)


def flat_state_partition(spec, shape, mesh):
    """The ONE divisibility rule for flat ZeRO bucket storage, shared by
    every spec consumer (executor GSPMD branch, spmd.DistConfig,
    plan_manual_dp): shard per `spec` ("dp" or an axes tuple like
    (None, "dp") for [L, padded] stacked buckets) when every sharded dim
    divides its mesh axis, else replicate."""
    from jax.sharding import PartitionSpec as P
    axes = spec_axes(spec)
    ok = shape is not None and len(shape) >= len(axes)
    for d, a in zip(shape or (), axes):
        if a is None:
            continue
        size = max(int(mesh.shape.get(a, 1)), 1)
        if not (d and d % size == 0):
            ok = False
    return P(*axes) if ok else P()


def plan_manual_dp(program, dist, mesh, block, fn, feed_meta, state_meta,
                   fetch_names, written_state, multi_k) -> \
        Optional[ManualDpPlan]:
    """Decide whether this (program, mesh, signature) runs the manual-dp
    bucketed step; returns the spec/gather plan or None for GSPMD.

    feed_meta / state_meta: {name: (shape, dtype)} of the GLOBAL arrays.
    `fn` is the runner partial (mut, ro, feeds, rng) -> (fetches, new_state);
    fetch shapes come from one eval_shape with LOCAL feed shapes.

    Structural declines are counted per cause under
    `executor.zero_manual_fallbacks.<cause>` (dp<=1 and unbucketed programs
    are normal operation, not fallbacks, and stay uncounted)."""
    import jax
    from jax.sharding import PartitionSpec as P

    if getattr(program, "_grad_buckets", None) is None or dist is None:
        return None
    dp = int(mesh.shape.get("dp", 1))
    if dp <= 1:
        return None
    for ax in ("tp", "pp", "sp", "ep"):
        if int(mesh.shape.get(ax, 1)) > 1:
            count_fallback("mixed_mesh")
            return None          # mixed meshes stay on GSPMD
    if getattr(program, "_microbatch_k", 0) and program._microbatch_k > 1:
        count_fallback("pipeline")
        return None
    cross_batch = _cross_batch_ops()
    for op_type in _iter_op_types(program):
        # sub_ops descs included: recompute/layer_scan fuse forward ops
        # into __segment__/__layer_scan__ bodies, and a cross-batch op
        # hidden there shards just as wrongly as a top-level one
        if op_type in cross_batch:
            from ..analysis.op_specs import cross_batch_cause
            count_fallback(cross_batch_cause(op_type))
            return None
    for b in program.blocks:
        for v in b.vars.values():
            if getattr(v, "_is_selected_rows", False):
                count_fallback("selected_rows")
                return None

    # feed specs: the dist config's own batch-axis decision, converted to
    # manual in_specs; at least one feed must actually shard over dp
    feed_specs = {}
    local_batch = None
    for name, (shape, _dt) in feed_meta.items():
        per_step = tuple(shape[1:]) if multi_k else tuple(shape)
        ns = dist.feed_sharding(mesh, name, per_step)
        spec = tuple(ns.spec)
        sharded = bool(spec) and spec[0] is not None
        if sharded:
            local_batch = per_step[0] // dp
        per_spec = P(*spec) if spec else P()
        feed_specs[name] = P(None, *per_spec) if multi_k else per_spec
    if local_batch is None:
        count_fallback("indivisible_batch")
        return None              # nothing sharded: manual buys nothing

    flat_state = dict(getattr(program, "_zero_state_specs", None) or {})
    zero_buckets = getattr(program, "_zero_buckets", None) or []
    zero_divides = all((b["padded"] % dp) == 0 for b in zero_buckets)
    if zero_buckets and not zero_divides:
        # a dp width the 64-element bucket padding does not divide — the
        # elastic-resume case of resuming onto an odd-sized slice: flat
        # state stays replicated and __zero_update__ runs full-width
        # (still averaging the grads), correct but unsharded, so count it
        # like every other structural decline
        count_fallback("indivisible_padding")

    def state_spec(name):
        ax = flat_state.get(name)
        if ax is not None and zero_divides:
            return P(*spec_axes(ax))
        return P()

    state_specs = {n: state_spec(n) for n in state_meta}
    written_specs = {n: state_spec(n) for n in written_state}

    # fetch avals: LOCAL feeds + FULL state (fetch batch-ness only depends
    # on the feeds; tracing here runs outside the manual context, where the
    # bucket ops are width-preserving)
    def _local_feed_aval(name):
        shape, dt = feed_meta[name]
        spec = feed_specs[name]
        shape = list(shape)
        bdim = 1 if multi_k else 0
        eff = tuple(spec)[bdim] if len(tuple(spec)) > bdim else None
        if eff is not None:
            shape[bdim] = shape[bdim] // dp
        return jax.ShapeDtypeStruct(tuple(shape), dt)

    # the mut/ro split does not change shapes: evaluate with all state mut
    mut_av = {n: jax.ShapeDtypeStruct(tuple(shape), dt)
              for n, (shape, dt) in state_meta.items()}
    feeds_av = {n: _local_feed_aval(n) for n in feed_meta}
    key_av = jax.eval_shape(lambda: jax.random.key(0))
    fetch_av, _ = jax.eval_shape(
        lambda mut, feeds, key: fn(mut, {}, feeds, key),
        mut_av, feeds_av, key_av)

    fetch_gathers = []
    for name, av in zip(fetch_names, fetch_av):
        shape = tuple(av.shape)
        eff = shape[1:] if multi_k else shape
        floating = np.issubdtype(np.dtype(av.dtype), np.floating)
        v = block.find_var_recursive(name)
        persistable = v is not None and v.persistable
        if len(eff) == 0:
            fetch_gathers.append(("pmean" if floating else "replicate",
                                  P()))
        elif eff[0] == local_batch and not persistable:
            # batch-leading activation: concat shards in global batch order
            spec = P(None, "dp") if multi_k else P("dp")
            fetch_gathers.append(("concat", spec))
        else:
            # params/state and non-batch tensors are replicated across
            # ranks by construction (pmean'd grads -> identical updates)
            fetch_gathers.append(("replicate", P()))
    return ManualDpPlan("dp", dp, mesh, feed_specs, state_specs,
                        fetch_gathers, written_specs, local_batch)


def build_manual_jit(plan: ManualDpPlan, fn, mut_names, ro_names,
                     donate: bool = True):
    """shard_map-wrap the runner per the plan and jit it with matching
    shardings. The returned callable has the _CompiledBlock.jitted signature
    (mut, ro, feeds, rng) -> (fetches, new_state)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    axis, dp, mesh = plan.axis, plan.dp, plan.mesh

    def body(mut, ro, feeds, rng):
        with _manual_ctx(axis, dp):
            fetches, new_state = fn(mut, ro, feeds, rng)
        out = []
        for f, (gather, _spec) in zip(fetches, plan.fetch_gathers):
            if gather == "pmean":
                f = jax.lax.pmean(f, axis)
            out.append(f)
        return out, new_state

    # out_specs mirror the output tree: fetch list + the written-state dict
    # (the donation floor may route small written buffers through ro — the
    # specs are keyed by NAME, so both splits resolve the same)
    in_specs = ({n: plan.state_specs[n] for n in mut_names},
                {n: plan.state_specs[n] for n in ro_names},
                dict(plan.feed_specs), P())
    out_specs = ([spec for _g, spec in plan.fetch_gathers],
                 dict(plan.written_specs))
    sm = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)

    def ns(spec):
        return NamedSharding(mesh, spec)

    jit_kw = {
        "in_shardings": ({n: ns(plan.state_specs[n]) for n in mut_names},
                         {n: ns(plan.state_specs[n]) for n in ro_names},
                         {n: ns(s) for n, s in plan.feed_specs.items()},
                         ns(P())),
        "out_shardings": ([ns(s) for _g, s in plan.fetch_gathers],
                          {n: ns(s)
                           for n, s in plan.written_specs.items()}),
    }
    return jax.jit(sm, donate_argnums=(0,) if donate else (), **jit_kw)
