"""Program-level strategy transforms: layer scan (rolled layers), recompute,
gradient merge.

Reference counterparts: the reference expresses repeated structure through
control-flow ops rather than unrolling (operators/controlflow/while_op.cc,
recurrent_op.cc); RecomputeOptimizer (optimizer.py:4547 + backward.py:689
_append_backward_ops_with_checkpoints_) and GradientMergeOptimizer
(optimizer.py:5025). TPU-native: `apply_layer_scan` rolls the N isomorphic
per-layer op segments of a deep model into ONE `__layer_scan__` op whose
lowering is a `lax.scan` over the per-layer weights stacked along a new
leading [L] axis — the compiled step program then contains each layer's HLO
once instead of N times (docs/perf_notes.md "Rolled-layer programs").
Recompute collapses a forward segment into ONE __segment__ op whose lowering
is wrapped in jax.checkpoint — the backward then holds only the segment's
boundary and the few values ops marked as dear to rebuild and cheap to hold
(ops/registry.py keep_under_recompute: a learned selection, its target, the
flash output, an expert layer's route), and re-runs the rest of the segment
(XLA schedules the rematerialization). Gradient merge gates the (arbitrary) optimizer update ops
with a step-counter mask using where-selects — no control-flow blocks needed.
"""
from __future__ import annotations

import contextlib
from typing import Dict, List, Optional

import numpy as np

import jax

from ..framework.program import OpRole, Operator, Parameter, Program
from ..ops import registry
from ..ops.registry import register

# Suffix of the stacked-per-layer parameter vars apply_layer_scan creates;
# sharding rules key on it (parallel/mesh.py: per-layer specs shift by one
# dim, the stacked [L] axis stays unsharded) and the Executor's scope
# round-trip restacks per-layer checkpoint entries under it.
LAYER_STACK_SUFFIX = "@LAYERS"


def _current_amp_dtype():
    """bf16/f16 when the program being lowered has static-graph AMP on —
    sub-graph ops (inside __segment__ / __layer_scan__) must apply the same
    white/black-list casts the top-level op loop applies."""
    from ..framework import executor as _ex
    if not _ex._lowering_programs:
        return None
    prog = _ex._lowering_programs[-1]
    if not getattr(prog, "_amp", False):
        return None
    import jax.numpy as jnp
    return (jnp.bfloat16
            if getattr(prog, "_amp_dtype", "bfloat16") == "bfloat16"
            else jnp.float16)


# ---------------------------------------------------------------------------
# __segment__: a fused sub-graph op (the recompute unit)
# ---------------------------------------------------------------------------

def _run_sub_ops(ctx, sub_ops, env, amp_dtype, seed_overrides=None):
    """Shared sub-graph interpreter for __segment__/__layer_scan__ bodies:
    applies each op desc's lowering over `env`, with the program's AMP
    casts (the top-level executor loop applies these per op; fused
    sub-graphs must match) and optional per-op __rng_seed__ overrides
    (traced per-layer seeds inside the scan body)."""
    from ..framework.executor import _amp_cast_ins, _op_timer, op_scopes
    for j, od in enumerate(sub_ops):
        opdef = registry.get(od["type"])
        op_ins = {s: [None if n == "@EMPTY@" else env[n] for n in ns]
                  for s, ns in od["inputs"].items()}
        at = od["attrs"]
        if seed_overrides is not None and seed_overrides[j] is not None:
            at = dict(at)
            at["__rng_seed__"] = seed_overrides[j]
        # the phase and program.name_scope, as the executor's own op loop
        # opens them: a group's device work keeps its names inside a
        # segment. _op_timer: into the walk's `by_op` table under the op's
        # own type, not the container's (executor.lower_block)
        with op_scopes(od["type"], at), _op_timer(od["type"], at):
            if amp_dtype is not None:
                op_ins = _amp_cast_ins(od["type"], op_ins, amp_dtype)
            outs = opdef.lower(ctx, op_ins, at)
        for s, ns in od["outputs"].items():
            if s not in outs:
                continue
            for n, v in zip(ns, outs[s]):
                if n == "@EMPTY@" or v is None:
                    continue
                env[n] = v
    return env


def _segment_fn(ctx, attrs):
    """The segment's sub-graph as a function of its input list."""
    sub_ops = attrs["sub_ops"]          # list of op descs
    in_names, out_names = attrs["in_names"], attrs["out_names"]
    amp_dtype = _current_amp_dtype()

    def run(in_vals):
        env = _run_sub_ops(ctx, sub_ops, dict(zip(in_names, in_vals)),
                           amp_dtype)
        return [env[n] for n in out_names]

    return run


def _segment_grad(ctx, ins, attrs, outs, ogs):
    """Grad rule of a recomputed segment: the pullback its forward lowering
    left (`_lower_segment`), so the forward is traced once. Declines where
    there is none (the forward was lowered in another walk: a pipeline
    stage's sections) and the generic `__vjp__` lowers the segment again."""
    made = ctx.pullbacks.pop(tuple(attrs["out_names"]), None)
    if made is None:
        return None
    out_vals, pullback = made
    return {"X": list(pullback(
        registry.cotangents(out_vals, ogs.get("Out", [])))[0])}


@register("__segment__", grad=_segment_grad)
def _lower_segment(ctx, ins, attrs):
    """The sub-graph under `jax.checkpoint`. In a program's own walk the
    lowering differentiates itself there and then (`jax.vjp`; the
    `__vjp__` op that follows takes the pullback, `_segment_grad`): ONE
    trace gives the forward its outputs and the backward its residuals,
    the segment's inputs and the values ops marked
    (`registry.keep_under_recompute`), so a kept value is made once.
    Lowered a second time by the generic `__vjp__` the forward pass's
    values and the differentiated pass's are two computations to XLA, and
    keeping would only move the work from the backward to the latter: the
    checkpoint there saves its inputs alone."""
    run = _segment_fn(ctx, attrs)
    if not attrs.get("remat", True):
        return {"Out": run(ins["X"])}
    if ctx.in_vjp or ctx.is_eval_shape:
        return {"Out": jax.checkpoint(run)(ins["X"])}
    with registry.recomputed(count=True):
        outs, pullback = jax.vjp(registry.checkpointed(run), ins["X"])
    ctx.pullbacks[tuple(attrs["out_names"])] = (outs, pullback)
    return {"Out": outs}


# ---------------------------------------------------------------------------
# __layer_scan__: N isomorphic layer segments rolled into one lax.scan
# ---------------------------------------------------------------------------

def _infer_layer_scan(block, op):
    """The scan carries one activation: Out is shaped exactly like X."""
    block.program.bump_version()
    vi = block.find_var_recursive(op.inputs["X"][0])
    vo = block.find_var_recursive(op.outputs["Out"][0])
    if vi is not None and vo is not None:
        vo.shape = tuple(vi.shape)
        vo.dtype = vi.dtype


@register("__layer_scan__", infer=_infer_layer_scan)
def _lower_layer_scan(ctx, ins, attrs):
    """ONE lax.scan over the [L]-stacked per-layer weights. The body is the
    template layer's op sequence; per-layer rng seeds ride the scan as xs
    (fold_in of a traced seed reproduces exactly the per-op masks the
    unrolled program draws, so rolled == unrolled bit-for-bit under
    dropout); remat=True wraps the body in jax.checkpoint — the standard
    JAX remat-per-layer pairing. The generic __vjp__ differentiates this
    lowering with jax.vjp, which transposes the scan into the backward
    scan — the compiled program contains each layer's HLO once in each
    direction."""
    import jax.numpy as jnp

    sub_ops = attrs["sub_ops"]
    n_layers = int(attrs["num_layers"])
    carry_in, carry_out = attrs["carry_in"], attrs["carry_out"]
    inv_env = dict(zip(attrs["inv_names"], ins.get("Inv", [])))
    stacked_names = attrs["stacked_names"]        # template (layer-0) names
    stacked_vals = tuple(ins.get("Stacked", []))
    seeds = tuple(None if s is None else jnp.asarray(s, jnp.uint32)
                  for s in attrs["layer_seeds"])
    amp_dtype = _current_amp_dtype()
    # ZeRO-3 stacked storage (parallel/zero.py): flagged stacked inputs are
    # [L, padded] flat buckets sharded over dp on the trailing axis — the
    # body all_gathers ONE layer slice per scan iteration (discarded after
    # use; the gather's jax.vjp transpose is a per-iteration psum_scatter,
    # so the stacked grads arrive pre-reduce-scattered)
    zero3 = attrs.get("zero3_flat") or [None] * len(stacked_names)

    def _materialize(sl, z):
        if z is None:
            return sl
        from .zero import current_manual_dp
        manual = current_manual_dp()
        if manual is not None and sl.shape[0] != int(z["padded"]):
            sl = jax.lax.all_gather(sl, manual[0], tiled=True)
        return jnp.reshape(jax.lax.slice(sl, (0,), (int(z["size"]),)),
                           tuple(z["shape"]))

    def body(carry, xs):
        slices, seed_slices = xs
        env = dict(inv_env)
        env[carry_in] = carry
        env.update({n: _materialize(sl, z)
                    for n, sl, z in zip(stacked_names, slices, zero3)})
        env = _run_sub_ops(ctx, sub_ops, env, amp_dtype,
                           seed_overrides=seed_slices)
        return env[carry_out], None

    remat = attrs.get("remat", False)
    # counted in the lowering that is differentiated (the generic __vjp__'s)
    with (registry.recomputed(ctx.in_vjp and not ctx.is_eval_shape, n_layers)
          if remat else contextlib.nullcontext()):
        carry, _ = jax.lax.scan(
            registry.checkpointed(body) if remat else body, ins["X"][0],
            (stacked_vals, seeds), length=n_layers)
    return {"Out": [carry]}


def sink_op_to_producers(block, op) -> int:
    """Move `op` EARLIER in the block's op list, to right after the last op
    it has a dataflow edge with: an op writing any of its inputs, or
    reading/writing any of its outputs. Used by the gradient-bucket
    pipeline (parallel/zero.py): a bucket's sync/update op placed at the
    backward→optimize boundary sinks back to its bucket's ready point — the
    moment its last gradient is produced — so XLA schedules the bucket's
    collective overlapping the backward compute that still runs for later
    buckets. Position only fixes dataflow order; the motion never crosses a
    producer of an input, a reader of an output, or another writer of an
    output, so program semantics are bit-identical."""
    ops = block.ops
    pos = ops.index(op)
    ins = {n for n in op.input_names() if n != "@EMPTY@"}
    outs = {n for n in op.output_names() if n != "@EMPTY@"}
    new = pos
    for i in range(pos - 1, -1, -1):
        other = ops[i]
        o_out = set(other.output_names())
        if (o_out & ins) or (o_out & outs) \
                or (set(other.input_names()) & outs):
            break
        new = i
    if new < pos:
        ops.pop(pos)
        ops.insert(new, op)
        block.program.bump_version()
    return new


def _attr_val_equal(a, b):
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        return (isinstance(a, np.ndarray) and isinstance(b, np.ndarray)
                and a.shape == b.shape and bool(np.array_equal(a, b)))
    return type(a) == type(b) and a == b            # noqa: E721


def _attrs_equal(a: dict, b: dict) -> bool:
    if set(a) != set(b):
        return False
    return all(_attr_val_equal(a[k], b[k]) for k in a)


class _SegmentMapper:
    """Builds the name correspondence template-segment -> segment i, or
    reports non-isomorphism. Two segments are isomorphic when their op
    sequences match type/slot/attr-wise (attrs modulo the per-op
    __rng_seed__) under a consistent bijective renaming of vars."""

    def __init__(self, template):
        self.template = template

    def map_segment(self, seg) -> Optional[Dict[str, str]]:
        if len(seg) != len(self.template):
            return None
        f: Dict[str, str] = {}
        rev: Dict[str, str] = {}

        def bind(n0, ni):
            if n0 == "@EMPTY@" or ni == "@EMPTY@":
                return n0 == ni
            if n0 in f:
                return f[n0] == ni
            if ni in rev:
                return False
            f[n0] = ni
            rev[ni] = n0
            return True

        for op0, opi in zip(self.template, seg):
            if op0.type != opi.type:
                return None
            if sorted(op0.inputs) != sorted(opi.inputs) \
                    or sorted(op0.outputs) != sorted(opi.outputs):
                return None
            a0 = {k: v for k, v in op0.attrs.items() if k != "__rng_seed__"}
            ai = {k: v for k, v in opi.attrs.items() if k != "__rng_seed__"}
            if not _attrs_equal(a0, ai):
                return None
            if ("__rng_seed__" in op0.attrs) != ("__rng_seed__" in opi.attrs):
                return None
            for slots0, slotsi in ((op0.inputs, opi.inputs),
                                   (op0.outputs, opi.outputs)):
                for slot in slots0:
                    if len(slots0[slot]) != len(slotsi[slot]):
                        return None
                    for n0, ni in zip(slots0[slot], slotsi[slot]):
                        if not bind(n0, ni):
                            return None
        return f


def _segment_externals(seg) -> List[str]:
    """Segment inputs produced outside it, in first-read order."""
    ext, seen, internal = [], set(), set()
    for op in seg:
        for n in op.input_names():
            if n != "@EMPTY@" and n not in internal and n not in seen:
                seen.add(n)
                ext.append(n)
        internal.update(n for n in op.output_names() if n != "@EMPTY@")
    return ext


def apply_layer_scan(program: Program, boundaries: List,
                     remat: bool = False, startup_program=None,
                     min_layers: int = 2) -> Optional[List[str]]:
    """Roll the N isomorphic per-layer segments ending at `boundaries` into
    one `__layer_scan__` op over [L]-stacked weights.

    `boundaries` are the per-layer output vars (the models' natural
    recompute checkpoints, `loss._layer_checkpoints`): segment i is the op
    run producing boundaries[i] from boundaries[i-1]. Segments are verified
    by op-topology isomorphism — equal op types/slots/attrs under a
    consistent renaming where the only renamed externals are the carried
    activation and per-layer persistable parameters. Anything else (MoE aux
    outputs consumed outside the layers, per-layer written persistables
    like BN stats, differing attrs such as pipeline_stage under pp) falls
    back to the unrolled program, untouched.

    Per-layer params are replaced by stacked `<layer0 name>@LAYERS` vars
    ([L, ...], the stacked axis unsharded under TP — parallel/mesh.py).
    When `startup_program` is given, a `stack` op is appended to it so the
    stacked value lands in the Scope at init (the per-layer init vars flip
    non-persistable there); the Executor also restacks lazily from
    per-layer Scope entries, so unrolled checkpoints load into rolled
    programs (framework/executor.py _ensure_stacked_params, run by
    Executor._resolve_call for every dispatch and inspection).

    Must run before append_backward. Returns the interior boundary names
    the roll consumed (callers drop them from recompute checkpoint lists —
    `remat=True` already rematerializes per layer), or None on fallback.
    """
    from ..analysis.passes import checked_pass
    with checked_pass("layer_scan", program,
                      startup_program=startup_program):
        return _apply_layer_scan(program, boundaries, remat=remat,
                                 startup_program=startup_program,
                                 min_layers=min_layers)


def _apply_layer_scan(program: Program, boundaries: List,
                      remat: bool = False, startup_program=None,
                      min_layers: int = 2) -> Optional[List[str]]:
    block = program.global_block()
    bounds = [b.name if hasattr(b, "name") else str(b) for b in boundaries]
    if len(bounds) < max(int(min_layers), 2):
        return None
    ops = block.ops
    assert all(op.attrs.get("op_role", 0) == OpRole.Forward for op in ops), \
        "apply_layer_scan must run before append_backward"

    producer = {}
    for idx, op in enumerate(ops):
        for n in op.output_names():
            if n != "@EMPTY@":
                producer[n] = idx
    if any(b not in producer for b in bounds):
        return None
    e = [producer[b] for b in bounds]
    n_layers = len(bounds)
    if any(e[i] >= e[i + 1] for i in range(n_layers - 1)):
        return None
    seg_len = e[1] - e[0]
    # equal spacing is the cheap pre-check; unequal op counts can never be
    # isomorphic (and fixes segment 0's start, which has no left boundary)
    if seg_len <= 0 or any(e[i + 1] - e[i] != seg_len
                           for i in range(n_layers - 1)):
        return None
    start0 = e[0] - seg_len + 1
    if start0 < 0:
        return None
    segments = [ops[e[i] - seg_len + 1: e[i] + 1] for i in range(n_layers)]

    template = segments[0]
    mapper = _SegmentMapper(template)
    maps = [None] + [mapper.map_segment(s) for s in segments[1:]]
    if any(m is None for m in maps[1:]):
        return None
    if any(maps[i].get(bounds[0]) != bounds[i] for i in range(1, n_layers)):
        return None

    # no segment may write a persistable (BN running stats etc.): those
    # would need scan-carry state threading the roll does not do
    for seg in segments:
        for op in seg:
            for n in op.output_names():
                v = block.find_var_recursive(n)
                if v is not None and v.persistable:
                    return None

    # classify template externals: loop-invariant / the carry / stacked
    externals = _segment_externals(template)
    carry_in = None
    stacked_templates: List[str] = []
    for n0 in externals:
        images = [maps[i].get(n0, n0) for i in range(1, n_layers)]
        if all(ni == n0 for ni in images):
            continue                                   # loop-invariant
        if images == bounds[:-1]:
            if carry_in is not None:
                return None                            # two carried vars
            carry_in = n0
            continue
        v0 = block.find_var_recursive(n0)
        if v0 is None or not v0.persistable:
            return None
        for ni in images:
            vi = block.find_var_recursive(ni)
            if vi is None or not vi.persistable \
                    or tuple(vi.shape) != tuple(v0.shape) \
                    or vi.dtype != v0.dtype \
                    or vi.trainable != v0.trainable \
                    or vi.stop_gradient != v0.stop_gradient:
                return None
        stacked_templates.append(n0)
    if carry_in is None:
        return None
    cv = block.find_var_recursive(carry_in)
    bv = block.find_var_recursive(bounds[0])
    if cv is None or bv is None or tuple(cv.shape) != tuple(bv.shape) \
            or cv.dtype != bv.dtype:
        return None

    # nothing produced inside the rolled region may be read outside it,
    # except the final boundary (the scan's Out)
    inner_produced = set()
    for seg in segments:
        for op in seg:
            inner_produced.update(n for n in op.output_names()
                                  if n != "@EMPTY@")
    inner_produced.discard(bounds[-1])
    outside_ops = ops[:start0] + ops[e[-1] + 1:]
    for op in outside_ops:
        if inner_produced & set(op.input_names()):
            return None

    inv_names = [n for n in externals
                 if n != carry_in and n not in stacked_templates]

    # template op descs (seeds stripped — they ride the scan as xs)
    sub_descs, layer_seeds = [], []
    for j, op0 in enumerate(template):
        at = {k: v for k, v in op0.attrs.items() if k != "__rng_seed__"}
        sub_descs.append({"type": op0.type,
                          "inputs": {k: list(v)
                                     for k, v in op0.inputs.items()},
                          "outputs": {k: list(v)
                                      for k, v in op0.outputs.items()},
                          "attrs": at})
        if "__rng_seed__" in op0.attrs:
            layer_seeds.append([int(segments[i][j].attrs["__rng_seed__"])
                                for i in range(n_layers)])
        else:
            layer_seeds.append(None)

    # stacked parameter vars (+ drop the now-dead per-layer Parameters)
    stacks: Dict[str, List[str]] = {}
    for n0 in stacked_templates:
        group = [n0] + [maps[i][n0] for i in range(1, n_layers)]
        tvar = block.var(n0)
        sname = n0 + LAYER_STACK_SUFFIX
        p = Parameter(block, name=sname,
                      shape=(n_layers,) + tuple(tvar.shape),
                      dtype=tvar.dtype, trainable=tvar.trainable)
        p.regularizer = getattr(tvar, "regularizer", None)
        if hasattr(tvar, "optimize_attrs"):
            p.optimize_attrs = dict(tvar.optimize_attrs)
        block.vars[sname] = p
        stacks[sname] = group
    for group in stacks.values():
        for n in group:
            block.vars.pop(n, None)

    scan_op = Operator(
        block, "__layer_scan__",
        {"X": [carry_in], "Inv": inv_names,
         "Stacked": [n0 + LAYER_STACK_SUFFIX for n0 in stacked_templates]},
        {"Out": [bounds[-1]]},
        {"sub_ops": sub_descs, "num_layers": n_layers,
         "carry_in": carry_in, "carry_out": bounds[0],
         "inv_names": inv_names, "stacked_names": list(stacked_templates),
         "layer_seeds": layer_seeds, "remat": bool(remat),
         "op_role": OpRole.Forward})
    block.ops = ops[:start0] + [scan_op] + ops[e[-1] + 1:]
    registry.infer_op(block, scan_op)

    program._layer_stacks = {**getattr(program, "_layer_stacks", {}),
                             **stacks}
    program.bump_version()

    if startup_program is not None:
        sb = startup_program.global_block()
        for sname, group in stacks.items():
            if not all(g in sb.vars for g in group):
                continue        # params initialized elsewhere: the
            for g in group:     # executor's lazy restack covers them
                sb.vars[g].persistable = False
            sv = block.var(sname)
            sb.create_var(name=sname, shape=sv.shape, dtype=sv.dtype,
                          persistable=True, stop_gradient=True)
            sb.append_op("stack", inputs={"X": list(group)},
                         outputs={"Y": [sname]}, attrs={"axis": 0})
        startup_program.bump_version()
    return bounds[:-1]


def apply_recompute(program: Program, checkpoints: List[str]):
    """Fuse forward ops into __segment__ ops split at checkpoint vars.

    Backward (__vjp__ of __segment__) then keeps the segment-boundary
    activations live and, beside them, the values the segment's ops marked
    with `registry.keep_under_recompute` (counter `recompute.kept_values`,
    gauge `recompute.kept_bytes`); everything else inside is recomputed.
    What a segment keeps costs, a layer of S positions in rows of B: 5 B S^2
    bytes where attention runs over a learned selection (its int8 mask and
    float32 target) and 2 B S heads head_dim (+ 4 B S heads) for a flash
    attention's output in bf16 and its logsumexp; a routed expert layer's
    choices are 20 bytes a (token, slot).
    """
    from ..analysis.passes import checked_pass
    with checked_pass("recompute", program):
        return _apply_recompute(program, checkpoints)


def _apply_recompute(program: Program, checkpoints: List[str]):
    block = program.global_block()
    ck = set(checkpoints)
    fwd_ops = [op for op in block.ops
               if op.attrs.get("op_role", 0) == OpRole.Forward]
    other_ops = [op for op in block.ops if op not in fwd_ops]
    assert not other_ops, "apply_recompute must run before append_backward"

    segments: List[List] = [[]]
    for op in fwd_ops:
        segments[-1].append(op)
        if ck & set(op.output_names()):
            segments.append([])
    if not segments[-1]:
        segments.pop()

    new_ops = []
    produced_so_far = set()
    for seg in segments:
        if len(seg) <= 1:
            new_ops.extend(seg)
            for op in seg:
                produced_so_far.update(op.output_names())
            continue
        seg_produced = set()
        seg_inputs, seg_outputs = [], []
        for op in seg:
            for n in op.input_names():
                if n not in seg_produced and n not in seg_inputs \
                        and n != "@EMPTY@":
                    seg_inputs.append(n)
            seg_produced.update(op.output_names())
        # outputs: vars visible after the segment (consumed later, fetched,
        # or checkpoints) — conservatively every produced var that any later
        # op reads, plus checkpoints
        later_reads = set()
        seen = False
        for s2 in segments:
            if s2 is seg:
                seen = True
                continue
            if seen:
                for op in s2:
                    later_reads.update(op.input_names())
        # dangling outputs (consumed by nothing yet — e.g. the loss, metric
        # outputs; backward/fetch will reference them after this transform)
        all_reads = set()
        for s2 in segments:
            for op in s2:
                all_reads.update(op.input_names())
        # in the order the segment's ops first produce them: the op's
        # output list reaches the step's HLO and with it the persistent
        # compile cache's key, so it may not follow a set of names
        for n in dict.fromkeys(n for op in seg for n in op.output_names()):
            if n in later_reads or n in ck or n not in all_reads:
                seg_outputs.append(n)
        sub_descs = [{"type": op.type, "inputs": op.inputs,
                      "outputs": op.outputs, "attrs": dict(op.attrs)}
                     for op in seg]
        from ..framework.program import Operator
        seg_op = Operator(block, "__segment__",
                          {"X": seg_inputs}, {"Out": seg_outputs},
                          {"sub_ops": sub_descs, "in_names": seg_inputs,
                           "out_names": seg_outputs, "remat": True,
                           "op_role": OpRole.Forward})
        new_ops.append(seg_op)
        produced_so_far.update(seg_produced)
    block.ops = new_ops
    program.bump_version()
    return program


# ---------------------------------------------------------------------------
# Gradient merge (micro-batch accumulation)
# ---------------------------------------------------------------------------

class GradientMergeWrapper:
    """Wraps any optimizer; accumulates grads k steps then applies the inner
    update, gating ALL inner-op state writes with a step mask (reference
    GradientMergeOptimizer semantics: moments only advance on merge steps)."""

    def __init__(self, inner, k_steps: int, avg: bool = True):
        self.inner = inner
        self.k = k_steps
        self.avg = avg
        self._step_var = None

    def __getattr__(self, item):
        return getattr(self.inner, item)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        params_grads = self.inner.backward(loss, startup_program,
                                           parameter_list, no_grad_set)
        self.apply_gradients_merged(loss.block.program, params_grads)
        return [], params_grads

    def apply_gradients_merged(self, program, params_grads):
        from ..analysis.passes import checked_pass
        with checked_pass("gradient_merge", program):
            return self._apply_gradients_merged(program, params_grads)

    def _apply_gradients_merged(self, program, params_grads):
        from .. import layers
        from ..framework import unique_name
        block = program.global_block()
        # gradient-merge gates every optimizer-state write behind where-
        # selects (outputs rewired to temps), so the optimizer section is
        # no longer the uniform per-param update the bucketing/ZeRO pass
        # (parallel/zero.py) rewrites — mark the program so the pass
        # declines it even when this wrapper was applied manually, outside
        # DistributedStrategy.gradient_merge
        program._grad_bucketing_unsafe = True
        merge_start = len(block.ops)  # everything appended below is Optimize

        step = layers.create_global_var([1], 0.0, "float32", persistable=True,
                                        name=unique_name.generate("gm_step"))
        step_new = layers.increment(step, value=1.0, in_place=False)
        layers.assign(step_new, step)
        k_var = layers.fill_constant([1], "float32", float(self.k))
        rem = layers.elementwise_mod(step, k_var)
        zero = layers.fill_constant([1], "float32", 0.0)
        apply_mask = layers.equal(rem, zero)           # bool [1]

        merged = []
        for p, g in params_grads:
            acc = layers.create_global_var(
                list(p.shape), 0.0, "float32", persistable=True,
                name=unique_name.generate(f"{p.name}_gm_acc"))
            acc_new = layers.sums([acc, g])
            eff = (layers.scale(acc_new, scale=1.0 / self.k) if self.avg
                   else acc_new)
            merged.append((p, eff))
            # reset accumulator on merge steps
            zeros = layers.zeros_like(acc)
            kept = layers.where(apply_mask, zeros, acc_new)
            layers.assign(kept, acc)

        # run inner update, then re-route its state writes through selects
        if self.inner._grad_clip is not None:
            merged = self.inner._grad_clip(merged)
        merged = self.inner._append_regularization(merged)
        self.inner._create_accumulators(block, [p for p, _ in merged])
        self.inner._create_lr_var()
        for p, g in merged:
            op = self.inner._append_optimize_op(block, (p, g))
            op.attrs["op_role"] = OpRole.Optimize
            self._gate_outputs(block, op, apply_mask)
        # epilogue ops (the shared adam beta-pow advance): gated like any
        # other state write — pows only move on merge steps, matching the
        # "moments only advance on merge steps" contract above
        for op in self.inner._finalize_optimize_ops(block):
            op.attrs["op_role"] = OpRole.Optimize
            self._gate_outputs(block, op, apply_mask)
        # tag exactly the ops this transform appended (counter/mask/acc/select
        # plumbing) — never forward ops of the same types elsewhere in the
        # graph, which clone(for_test) would then wrongly prune
        for op in block.ops[merge_start:]:
            if op.attrs.get("op_role", 0) == 0:
                op.attrs["op_role"] = OpRole.Optimize

    def _gate_outputs(self, block, op, mask_var):
        """Rewrite op outputs to temps, then out = where(mask, temp, old)."""
        from ..framework import unique_name
        pairs = []
        for slot, names in op.outputs.items():
            for i, n in enumerate(names):
                tmp = block.create_var(
                    name=unique_name.generate(f"{n}_gated"),
                    shape=block.var(n).shape, dtype=block.var(n).dtype,
                    stop_gradient=True)
                pairs.append((n, tmp.name))
                names[i] = tmp.name
        for orig, tmp in pairs:
            block.append_op("where",
                            inputs={"Condition": [mask_var.name],
                                    "X": [tmp], "Y": [orig]},
                            outputs={"Out": [orig]},
                            attrs={"op_role": OpRole.Optimize})
        block.program.bump_version()


class RecomputeWrapper:
    """Optimizer wrapper applying activation checkpointing before backward
    (reference optimizer.py:4547 RecomputeOptimizer; fleet meta-optimizer
    recompute_optimizer.py). Forward ops collapse into __segment__ ops with
    remat=True: the checkpoint activations stay live and, beside each, what
    the segment's ops marked as kept (`apply_recompute`: a sparse-attention
    layer's selection and target, 5 B S^2 bytes, a flash attention's output,
    2 B S heads head_dim); the rest is recomputed in the backward."""

    def __init__(self, inner, checkpoints):
        self._inner = inner
        self._checkpoints = [c.name if hasattr(c, "name") else c
                             for c in checkpoints]

    def _set_checkpoints(self, checkpoints):
        self._checkpoints = [c.name if hasattr(c, "name") else c
                             for c in checkpoints]

    def __getattr__(self, item):
        return getattr(self._inner, item)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from ..framework.program import default_main_program
        apply_recompute(default_main_program(), self._checkpoints)
        return self._inner.minimize(loss, startup_program, parameter_list,
                                    no_grad_set)
