"""Static sharding-spec propagation: the front-end of the auto-parallel
planner (ROADMAP item 4).

Reference counterpart: the auto_parallel completion pass — the reference
walks a program op-by-op completing every var's DistAttr from per-op SPMD
rules
(elementwise-follows-input, matmul contraction, embedding row/col split)
before any partitioner runs; Alpa/GSPMD (PAPERS.md) build the same layer
under every auto-parallel planner. This module is that front-end for THIS
repo's Program IR: given a **plan point** (mesh shape × the program's
baked-in sharding stage × bucket layout), it infers a ShardSpec for every
var WITHOUT compiling anything, and emits typed Findings for

* incoherent specs / implicit reshards on the hot path (an op whose input
  specs force GSPMD to insert a gather/reshard),
* ops with no declared propagation rule (coverage debt, so the zoo lint
  can run coverage-as-errors),
* the structural fallback matrix — every cause that today silently drops
  the manual-dp shard_map path at run time (counted under
  `executor.zero_manual_fallbacks.<cause>`) becomes a build-time Finding
  NAMING the op and the runtime counter it predicts,
* illegal plan compositions (stage3+tp; cross-batch ops under a strict
  manual-dp plan) — rejected before any compile.

The per-op rules live in ONE table: `RULES` here, keyed by the `sharding`
field of each registry OpSpec (analysis/op_specs.py); parallel/zero.py
sources its cross-batch decline set from the same spec table
(`op_specs.cross_batch_ops`), so the static lint and the runtime fallback
can never drift apart.

Specs are plain tuples — one mesh-axis name (or None) per dim, the static
mirror of jax PartitionSpec. `()` means replicated/scalar.

`analysis/cost.py` builds the compile-free collective/memory predictor on
top of the propagation result. CLI: `scripts/program_lint.py --mesh ...`.
Docs: docs/static_analysis.md "Sharding & cost analysis".
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .findings import Finding

EMPTY = "@EMPTY@"

Spec = Tuple  # per-dim mesh axis name or None; () = replicated / scalar

# ---------------------------------------------------------------------------
# the fallback matrix: structural causes that drop the manual-dp shard_map
# path at run time, each with the monitor counter the lint warning predicts
# (parallel/zero.py count_fallback emits these exact names)
# ---------------------------------------------------------------------------

FALLBACK_COUNTERS: Dict[str, str] = {
    "cross_batch": "executor.zero_manual_fallbacks.cross_batch",
    "batch_norm": "executor.zero_manual_fallbacks.batch_norm",
    "selected_rows": "executor.zero_manual_fallbacks.selected_rows",
    "mixed_mesh": "executor.zero_manual_fallbacks.mixed_mesh",
    "pipeline": "executor.zero_manual_fallbacks.pipeline",
    "indivisible_batch": "executor.zero_manual_fallbacks.indivisible_batch",
    "indivisible_padding":
        "executor.zero_manual_fallbacks.indivisible_padding",
}


@dataclass
class PlanPoint:
    """One point of the (mesh shape × stage × bucket) plan space.

    The sharding stage and bucket layout are read from the program itself
    (`program._grad_buckets`, baked in by fleet minimize); the plan point
    adds the MESH question — what does this program cost / shard like on
    a dp=A×tp=B×... mesh — plus the optional knowledge needed to resolve
    batch-polymorphic dims and TP parameter placement.
    """
    mesh_axes: Dict[str, int] = field(default_factory=dict)
    param_rules: object = None        # parallel.mesh.ShardingRules or None
    batch: Optional[int] = None       # global batch for -1 feed dims
    batch_axes: Sequence[str] = ("dp",)

    def axis(self, name: str) -> int:
        return max(int(self.mesh_axes.get(name, 1)), 1)

    @property
    def dp(self) -> int:
        return self.axis("dp")

    @property
    def ndev(self) -> int:
        n = 1
        for v in self.mesh_axes.values():
            n *= max(int(v), 1)
        return n

    @property
    def dp_pure(self) -> bool:
        return all(self.axis(a) <= 1
                   for a in self.mesh_axes if a not in ("dp",))

    def describe(self) -> str:
        return " ".join(f"{k}={v}" for k, v in sorted(self.mesh_axes.items())
                        if v > 1) or "single"


def parse_mesh(text: str) -> Dict[str, int]:
    """'dp=2,tp=2' -> {'dp': 2, 'tp': 2} (the --mesh CLI syntax)."""
    axes: Dict[str, int] = {}
    for part in str(text).split(","):
        part = part.strip()
        if not part:
            continue
        k, _, v = part.partition("=")
        axes[k.strip()] = int(v)
    return axes


@dataclass
class PropagationResult:
    specs: Dict[str, Spec]
    findings: List[Finding]
    # collective-materialization events the propagation predicts GSPMD (or
    # the manual runner) would insert: {kind, nbytes, op_index, op_type,
    # origin, phase} — analysis/cost.py turns these into cost entries
    events: List[dict]

    def spec(self, name: str) -> Spec:
        return self.specs.get(name, ())


# ---------------------------------------------------------------------------
# spec algebra helpers
# ---------------------------------------------------------------------------

def _shape(block, name):
    v = None if name == EMPTY else block.find_var_recursive(name)
    return tuple(v.shape) if v is not None else None


def _numel(shape, batch=None) -> int:
    n = 1
    for d in shape or ():
        d = int(d)
        if d < 0:
            d = batch if batch else 1
        n *= max(d, 1)
    return n


def _fit(spec: Spec, ndim: Optional[int]) -> Spec:
    """Clip/pad a spec to `ndim` entries (trailing Nones implied)."""
    if ndim is None:
        return tuple(spec)
    spec = tuple(spec)[:ndim]
    return spec + (None,) * (ndim - len(spec))


def _sharded(spec: Spec) -> bool:
    return any(a is not None for a in spec)


def _join(a: Spec, b: Spec, ndim: int) -> Tuple[Spec, bool]:
    """Broadcast-join two input specs (trailing-dim alignment); returns
    (joined spec, conflict?) — conflict means the two inputs are sharded
    differently on the same dim and one must be resharded."""
    a, b = _fit(a, ndim), _fit(b, ndim)
    out, conflict = [], False
    for ax, bx in zip(a, b):
        if ax == bx or bx is None:
            out.append(ax)
        elif ax is None:
            out.append(bx)
        else:
            conflict = True
            out.append(ax)
    return tuple(out), conflict


class _Ctx:
    """Propagation state handed to every rule."""

    def __init__(self, program, block, plan: PlanPoint):
        self.program = program
        self.block = block
        self.plan = plan
        self.specs: Dict[str, Spec] = {}
        self.findings: List[Finding] = []
        self.events: List[dict] = []
        self._warned_rules: set = set()
        self._emitted: set = set()

    def spec_of(self, name: str) -> Spec:
        return self.specs.get(name, ())

    def set_spec(self, name: str, spec: Spec) -> None:
        if name != EMPTY:
            self.specs[name] = tuple(spec)

    def emit(self, check, severity, message, op_index=None, op_type=None,
             var=None):
        # sub-graph bodies repeat per layer: identical findings dedupe
        key = (check, message, op_index, op_type, var)
        if key in self._emitted:
            return
        self._emitted.add(key)
        self.findings.append(Finding(
            check=check, severity=severity, message=message,
            block=self.block.idx, op_index=op_index, op_type=op_type,
            var=var))

    def event(self, kind, nbytes, op_index, op_type, origin, phase="fwd"):
        self.events.append({"kind": kind, "nbytes": int(max(nbytes, 0)),
                            "op_index": op_index, "op_type": op_type,
                            "origin": origin, "phase": phase})

    def pdev_numel(self, shape, spec: Spec) -> int:
        """Per-device element count of `shape` under `spec`."""
        n = 1
        for i, d in enumerate(shape or ()):
            d = int(d)
            if d < 0:
                d = self.plan.batch or self.plan.dp
            d = max(d, 1)
            ax = spec[i] if i < len(spec) else None
            if ax is not None:
                size = self.plan.axis(ax) if isinstance(ax, str) else \
                    int(np.prod([self.plan.axis(a) for a in ax]))
                if size > 1 and d % size == 0:
                    d //= size
            n *= d
        return n


# ---------------------------------------------------------------------------
# per-op propagation rules (RULES[name] <- OpSpec.sharding)
# ---------------------------------------------------------------------------

def _first_in(op):
    for slot in ("X", "Input", "Logits", "Q"):
        names = op.inputs.get(slot)
        if names:
            return names[0]
    for names in op.inputs.values():
        if names:
            return names[0]
    return EMPTY


def _set_all_outputs(ctx, op, spec: Spec):
    for slot, names in op.outputs.items():
        for n in names:
            shape = _shape(ctx.block, n)
            ctx.set_spec(n, _fit(spec, len(shape) if shape is not None
                                 else None))


def _rule_follow_x(ctx, i, op):
    _set_all_outputs(ctx, op, ctx.spec_of(_first_in(op)))


def _rule_replicated(ctx, i, op):
    _set_all_outputs(ctx, op, ())


def _rule_elementwise(ctx, i, op):
    names = [n for names in op.inputs.values() for n in names if n != EMPTY]
    out_name = next((n for names in op.outputs.values() for n in names
                     if n != EMPTY), EMPTY)
    shape = _shape(ctx.block, out_name)
    ndim = len(shape) if shape is not None else max(
        (len(ctx.spec_of(n)) for n in names), default=0)
    spec: Spec = ()
    for n in names:
        # broadcasting aligns trailing dims: left-pad the shorter operand
        s = ctx.spec_of(n)
        nshape = _shape(ctx.block, n)
        if nshape is not None and len(nshape) < ndim:
            s = (None,) * (ndim - len(nshape)) + _fit(s, len(nshape))
        spec, conflict = _join(spec, s, ndim)
        if conflict:
            ctx.emit("spec_conflict", "warning",
                     f"operands of {op.type!r} are sharded differently "
                     f"({n!r} disagrees with the joined spec {spec}): one "
                     "side is resharded before the op runs",
                     i, op.type, n)
            ctx.event("all-gather",
                      ctx.pdev_numel(nshape, ()) * 4, i, op.type,
                      "operand_reshard")
    _set_all_outputs(ctx, op, spec)


def _matmul_dims(ctx, op):
    """(x_batch_spec, x_contract_axis, y_contract_axis, y_out_spec) for
    matmul/mul, honoring transpose flags and mul's num_col_dims."""
    xn = (op.inputs.get("X") or [EMPTY])[0]
    yn = (op.inputs.get("Y") or [EMPTY])[0]
    xs, ys = ctx.spec_of(xn), ctx.spec_of(yn)
    xsh, ysh = _shape(ctx.block, xn), _shape(ctx.block, yn)
    xs = _fit(xs, len(xsh) if xsh else len(xs))
    ys = _fit(ys, len(ysh) if ysh else len(ys))
    if op.type == "mul":
        m = int(op.attrs.get("x_num_col_dims", 1))
        batch = tuple(xs[:m])
        x_k = xs[-1] if len(xs) > m else None
        y_k = ys[0] if ys else None
        y_out = tuple(ys[1:])
    else:
        tx = bool(op.attrs.get("transpose_X", False))
        ty = bool(op.attrs.get("transpose_Y", False))
        batch = tuple(xs[:-2]) + ((xs[-1],) if tx else (xs[-2],)) \
            if len(xs) >= 2 else tuple(xs[:-1])
        x_k = (xs[-2] if tx else xs[-1]) if xs else None
        if ty:
            y_k = ys[-1] if ys else None
            y_out = tuple(ys[:-1][-1:])
        else:
            y_k = ys[-2] if len(ys) >= 2 else (ys[0] if ys else None)
            y_out = tuple(ys[-1:])
    return batch, x_k, y_k, y_out, xn, yn


def _rule_matmul(ctx, i, op, backward=False):
    batch, x_k, y_k, y_out, xn, yn = _matmul_dims(ctx, op)
    out_name = (op.outputs.get("Out") or [EMPTY])[0]
    out_shape = _shape(ctx.block, out_name)
    # leading out dims come from X's batch dims, trailing from Y: pad on
    # the RIGHT when Y's rank is unknown (trailing dims default unsharded)
    spec = tuple(batch) + tuple(y_out)
    if out_shape is not None:
        spec = _fit(spec, len(out_shape))
    if x_k is not None and y_k is not None and x_k == y_k:
        # contracted dim sharded on both sides (Megatron row-parallel):
        # the product is a partial sum — GSPMD must all-reduce the output
        nb = ctx.pdev_numel(out_shape, spec) * 4
        ctx.event("all-reduce", nb, i, op.type, "matmul_contraction")
    elif x_k is not None and y_k is not None and x_k != y_k:
        ctx.emit("spec_conflict", "warning",
                 f"{op.type!r} contracts a dim sharded {x_k!r} on X but "
                 f"{y_k!r} on Y — one operand is resharded",
                 i, op.type, xn)
    _set_all_outputs(ctx, op, spec)
    ctx.set_spec(out_name, spec)


def _rule_reduce_all(ctx, i, op):
    _set_all_outputs(ctx, op, ())


def _rule_softmax_ce(ctx, i, op):
    ls = ctx.spec_of((op.inputs.get("Logits") or [EMPTY])[0])
    for n in op.outputs.get("Softmax", ()):
        ctx.set_spec(n, ls)
    for n in op.outputs.get("Loss", ()):
        shape = _shape(ctx.block, n)
        ctx.set_spec(n, _fit(ls, len(shape) if shape is not None
                             else max(len(ls) - 1, 0)))


def _rule_reshape(ctx, i, op):
    xn = _first_in(op)
    xs = ctx.spec_of(xn)
    xsh = _shape(ctx.block, xn)
    out_name = (op.outputs.get("Out") or [EMPTY])[0]
    osh = _shape(ctx.block, out_name)
    spec = [None] * (len(osh) if osh is not None else 0)
    lost = False
    if osh is not None and xsh is not None and xs:
        # leading-dim sharding survives a reshape that keeps the leading
        # extent divisible (merging [B,S,..]->[B*S,..] or splitting back)
        ax = xs[0] if xs else None
        if ax is not None and spec:
            size = ctx.plan.axis(ax)
            d0 = int(osh[0]) if int(osh[0]) > 0 else (ctx.plan.batch or 0)
            if d0 == 0 or d0 % max(size, 1) == 0:
                spec[0] = ax
            else:
                lost = True
        # a trailing dim of identical extent keeps its spec (TP activations)
        if len(xs) == len(xsh) and xsh and osh and \
                int(xsh[-1]) == int(osh[-1]) and xs[-1] is not None \
                and len(spec) >= 1:
            spec[-1] = xs[-1]
        elif any(a is not None for a in xs[1:]):
            lost = True
    if lost:
        ctx.emit("implicit_reshard", "warning",
                 f"{op.type!r} destroys the input sharding {tuple(xs)} "
                 f"(shape {xsh} -> {osh}): the value is gathered before "
                 "the reshape", i, op.type, xn)
        ctx.event("all-gather", ctx.pdev_numel(xsh, ()) * 4, i, op.type,
                  "reshape_gather")
    for slot, names in op.outputs.items():
        for n in names:
            ctx.set_spec(n, tuple(spec) if slot == "Out" else ())


def _rule_transpose(ctx, i, op):
    xn = _first_in(op)
    xs = ctx.spec_of(xn)
    xsh = _shape(ctx.block, xn)
    axis = list(op.attrs.get("axis") or ())
    xs = _fit(xs, len(xsh) if xsh is not None else len(axis))
    spec = tuple(xs[a] for a in axis) if axis and len(axis) <= len(xs) \
        else ()
    for slot, names in op.outputs.items():
        for n in names:
            ctx.set_spec(n, spec if slot == "Out" else ())


def _rule_unsqueeze(ctx, i, op):
    xn = _first_in(op)
    xs = list(_fit(ctx.spec_of(xn), len(_shape(ctx.block, xn) or ())))
    for a in sorted(int(a) for a in (op.attrs.get("axes") or ())):
        a = a if a >= 0 else a + len(xs) + 1
        xs.insert(min(max(a, 0), len(xs)), None)
    for slot, names in op.outputs.items():
        for n in names:
            ctx.set_spec(n, tuple(xs) if slot == "Out" else ())


def _rule_slice(ctx, i, op):
    xn = _first_in(op)
    xsh = _shape(ctx.block, xn)
    spec = list(_fit(ctx.spec_of(xn), len(xsh or ())))
    for a in (op.attrs.get("axes") or ()):
        a = int(a)
        if 0 <= a < len(spec) and spec[a] is not None:
            ctx.emit("implicit_reshard", "warning",
                     f"slice along dim {a}, which is sharded "
                     f"{spec[a]!r}: the dim is gathered first",
                     i, op.type, xn)
            ctx.event("all-gather", ctx.pdev_numel(xsh, ()) * 4, i,
                      op.type, "slice_gather")
            spec[a] = None
    drop = sorted((int(a) for a in (op.attrs.get("decrease_axis") or ())),
                  reverse=True)
    for a in drop:
        if 0 <= a < len(spec):
            del spec[a]
    _set_all_outputs(ctx, op, tuple(spec))


def _rule_split(ctx, i, op):
    xn = _first_in(op)
    spec = list(_fit(ctx.spec_of(xn), len(_shape(ctx.block, xn) or ())))
    a = int(op.attrs.get("axis", 0))
    if 0 <= a < len(spec) and spec[a] is not None:
        ctx.emit("implicit_reshard", "warning",
                 f"split along sharded dim {a} ({spec[a]!r}): gathered "
                 "before the split", i, op.type, xn)
        spec[a] = None
    _set_all_outputs(ctx, op, tuple(spec))


def _rule_concat(ctx, i, op):
    names = [n for n in op.inputs.get("X", ()) if n != EMPTY]
    ndim = len(_shape(ctx.block, names[0]) or ()) if names else 0
    spec: Spec = ()
    for n in names:
        spec, _ = _join(spec, ctx.spec_of(n), ndim)
    spec = list(_fit(spec, ndim))
    a = int(op.attrs.get("axis", 0))
    if 0 <= a < len(spec) and spec[a] is not None:
        spec[a] = None
    _set_all_outputs(ctx, op, tuple(spec))


def _rule_stack(ctx, i, op):
    names = [n for n in op.inputs.get("X", ()) if n != EMPTY]
    ndim = len(_shape(ctx.block, names[0]) or ()) if names else 0
    spec: Spec = ()
    for n in names:
        spec, _ = _join(spec, ctx.spec_of(n), ndim)
    a = int(op.attrs.get("axis", 0))
    out = list(_fit(spec, ndim))
    out.insert(min(max(a, 0), len(out)), None)
    _set_all_outputs(ctx, op, tuple(out))


def _rule_gather(ctx, i, op):
    xn = (op.inputs.get("X") or [EMPTY])[0]
    idxn = (op.inputs.get("Index") or [EMPTY])[0]
    xs = _fit(ctx.spec_of(xn), len(_shape(ctx.block, xn) or ()))
    if xs and xs[0] is not None:
        out_shape = _shape(ctx.block,
                           (op.outputs.get("Out") or [EMPTY])[0])
        ctx.event("all-reduce", ctx.pdev_numel(out_shape, ()) * 4, i,
                  op.type, "sharded_gather")
    spec = _fit(ctx.spec_of(idxn),
                len(_shape(ctx.block, idxn) or ())) + tuple(xs[1:])
    _set_all_outputs(ctx, op, spec)


def _rule_lookup(ctx, i, op):
    wn = (op.inputs.get("W") or [EMPTY])[0]
    idn = (op.inputs.get("Ids") or [EMPTY])[0]
    ws = _fit(ctx.spec_of(wn), len(_shape(ctx.block, wn) or (0, 0)))
    ids_spec = _fit(ctx.spec_of(idn), len(_shape(ctx.block, idn) or ()))
    idsh = _shape(ctx.block, idn)
    if idsh and int(idsh[-1]) == 1:          # trailing [.., 1] ids dim
        ids_spec = ids_spec[:-1]
    out_name = (op.outputs.get("Out") or [EMPTY])[0]
    spec = tuple(ids_spec) + tuple(ws[1:])
    if ws and ws[0] is not None:
        # vocab-parallel embedding: each shard contributes the rows it
        # owns; GSPMD masks + all-reduces the gathered activations
        out_shape = _shape(ctx.block, out_name)
        ctx.event("all-reduce",
                  ctx.pdev_numel(out_shape, spec) * 4, i, op.type,
                  "vocab_parallel_embedding")
    ctx.set_spec(out_name, spec)


def _rule_layer_norm(ctx, i, op):
    xs = ctx.spec_of((op.inputs.get("X") or [EMPTY])[0])
    bna = int(op.attrs.get("begin_norm_axis", 1))
    for n in op.outputs.get("Y", ()):
        ctx.set_spec(n, xs)
    stat = _fit(xs, bna)
    for slot in ("Mean", "Variance"):
        for n in op.outputs.get(slot, ()):
            ctx.set_spec(n, stat)


def _rule_attention(ctx, i, op):
    spec = ctx.spec_of((op.inputs.get("Q") or [EMPTY])[0])
    _set_all_outputs(ctx, op, spec)
    if op.attrs.get("layout") == "bshd" and len(spec) >= 3:
        # Q is [B, S, heads, hd] there and Lse [B, heads, S] all the same
        for n in op.outputs.get("Lse", ()):
            ctx.set_spec(n, (spec[0], spec[2], spec[1]))


def _rule_moe(ctx, i, op):
    xs = ctx.spec_of((op.inputs.get("X") or [EMPTY])[0])
    for n in op.outputs.get("Out", ()):
        ctx.set_spec(n, xs)
    for slot in ("AuxLoss", "GateIdx", "TopIdx", "ExpertLoad"):
        for n in op.outputs.get(slot, ()):
            ctx.set_spec(n, ())


def _rule_auc(ctx, i, op):
    _set_all_outputs(ctx, op, ())


def _rule_param_update(ctx, i, op):
    pn = (op.inputs.get("Param") or [EMPTY])[0]
    ps = ctx.spec_of(pn)
    gn = (op.inputs.get("Grad") or [EMPTY])[0]
    gs = ctx.spec_of(gn)
    ndim = max(len(ps), len(gs))
    if _fit(ps, ndim) != _fit(gs, ndim):
        ctx.emit("spec_conflict", "warning",
                 f"update reads Param {pn!r} sharded {tuple(ps)} but Grad "
                 f"{gn!r} sharded {tuple(gs)}: the gradient is resharded "
                 "before the update", i, op.type, pn)
    for slot, names in op.outputs.items():
        for n, src in zip(names, op.inputs.get(
                slot.replace("Out", ""), op.inputs.get("Param", ()))):
            ctx.set_spec(n, ctx.spec_of(src))


def _rule_selected_rows(ctx, i, op):
    _set_all_outputs(ctx, op, ())


RULES = {
    "follow_x": _rule_follow_x,
    "replicated": _rule_replicated,
    "elementwise": _rule_elementwise,
    "matmul": _rule_matmul,
    "reduce_all": _rule_reduce_all,
    "softmax_ce": _rule_softmax_ce,
    "reshape": _rule_reshape,
    "transpose": _rule_transpose,
    "unsqueeze": _rule_unsqueeze,
    "slice": _rule_slice,
    "split": _rule_split,
    "concat": _rule_concat,
    "stack": _rule_stack,
    "gather": _rule_gather,
    "lookup": _rule_lookup,
    "layer_norm": _rule_layer_norm,
    "attention": _rule_attention,
    "moe": _rule_moe,
    "auc": _rule_auc,
    "param_update": _rule_param_update,
    "selected_rows": _rule_selected_rows,
}


# ---------------------------------------------------------------------------
# structural ops (dispatched on op.type, before the spec rule table)
# ---------------------------------------------------------------------------

def _struct_bucket_sync(ctx, i, op):
    for xn, on in zip(op.inputs.get("X", ()), op.outputs.get("Out", ())):
        ctx.set_spec(on, ctx.spec_of(xn))


def _struct_zero_update(ctx, i, op):
    for n, src in zip(op.outputs.get("ParamOut", ()),
                      op.inputs.get("Param", ())):
        ctx.set_spec(n, ctx.spec_of(src))
    for slot_out, slot_in in (("FlatStateOut", "FlatState"),
                              ("FlatParamOut", "FlatParam")):
        for n, src in zip(op.outputs.get(slot_out, ()),
                          op.inputs.get(slot_in, ())):
            ctx.set_spec(n, ctx.spec_of(src))
    for n in op.outputs.get("FlatGradOut", ()):
        # the resident averaged-gradient shard mirrors the flat state spec
        flat = op.inputs.get("FlatState") or op.inputs.get("FlatParam") or ()
        ctx.set_spec(n, ctx.spec_of(flat[0]) if flat else ())


def _struct_zero_gather(ctx, i, op):
    for n in op.outputs.get("Out", ()):
        ctx.set_spec(n, ())          # gathered full-width per-param views


def _struct_zero_pack(ctx, i, op):
    for n in op.outputs.get("Out", ()):
        ctx.set_spec(n, ctx.specs.get(n, ("dp",)))


def _struct_segment(ctx, i, op):
    for od in op.attrs.get("sub_ops") or ():
        _propagate_desc(ctx, i, od)


def _struct_layer_scan(ctx, i, op):
    # the body sees per-layer SLICES of [L, ...] stacked inputs: the spec
    # shifts one dim left (the @LAYERS stacked-axis shift); zero3 flat
    # stacked storage ((None, 'dp')) is all-gathered per iteration, so the
    # body's view is replicated
    stacked = list(op.attrs.get("stacked_names") or ())
    z3 = list(op.attrs.get("zero3_flat") or [None] * len(stacked))
    for name, sname, z in zip(op.inputs.get("Stacked", ()), stacked,
                              z3 + [None] * len(stacked)):
        spec = ctx.spec_of(name)
        ctx.set_spec(sname, () if z else tuple(spec[1:]))
    carry_in = op.attrs.get("carry_in")
    xs = op.inputs.get("X", ())
    if carry_in and xs:
        ctx.set_spec(carry_in, ctx.spec_of(xs[0]))
    for od in op.attrs.get("sub_ops") or ():
        _propagate_desc(ctx, i, od)
    carry_out = op.attrs.get("carry_out")
    for n in op.outputs.get("Out", ()):
        ctx.set_spec(n, ctx.spec_of(carry_out) if carry_out else ())


def _struct_vjp(ctx, i, op):
    # grad specs mirror the forward inputs (the vjp transposes collectives:
    # a per-iteration all_gather becomes a per-iteration psum_scatter, so
    # sharded storage gets back sharded gradients)
    for slot, names in op.outputs.items():
        if not slot.startswith("IG:"):
            continue
        for gn, fn in zip(names, op.inputs.get(slot[3:], ())):
            ctx.set_spec(gn, ctx.spec_of(fn))
    fwd = op.attrs.get("fwd_type")
    if fwd in ("matmul", "mul"):
        # Megatron column-parallel backward: dX = dOut @ Y^T contracts the
        # tp-sharded output dim -> partial sum over tp
        yn = (op.inputs.get("Y") or [EMPTY])[0]
        ys = ctx.spec_of(yn)
        out_ax = ys[-1] if ys else None
        if out_ax is not None:
            xn = (op.inputs.get("X") or [EMPTY])[0]
            xsh = _shape(ctx.block, xn)
            ctx.event("all-reduce",
                      ctx.pdev_numel(xsh, ctx.spec_of(xn)) * 4, i,
                      "__vjp__", "matmul_contraction", phase="bwd")


def _struct_control_flow(ctx, i, op):
    # sub-block control flow: conservative — carried/branch outputs are
    # treated as replicated (collective placement inside sub-blocks is
    # check_collectives' concern, not the cost model's)
    _set_all_outputs(ctx, op, ())


_STRUCTURAL = {
    "__bucket_sync__": _struct_bucket_sync,
    "__zero_update__": _struct_zero_update,
    "__zero_gather__": _struct_zero_gather,
    "__zero_pack__": _struct_zero_pack,
    "__segment__": _struct_segment,
    "__layer_scan__": _struct_layer_scan,
    "__vjp__": _struct_vjp,
    "__cond__": _struct_control_flow,
    "__while__": _struct_control_flow,
    "__scan__": _struct_control_flow,
}


class _DescOp:
    """Adapter presenting a sub_ops desc dict with the Operator surface the
    rules read (type/inputs/outputs/attrs)."""

    __slots__ = ("type", "inputs", "outputs", "attrs")

    def __init__(self, od):
        self.type = od.get("type")
        self.inputs = od.get("inputs", {})
        self.outputs = od.get("outputs", {})
        self.attrs = od.get("attrs", {})


def _propagate_desc(ctx, i, od):
    _propagate_op(ctx, i, _DescOp(od))


def _propagate_op(ctx, i, op):
    handler = _STRUCTURAL.get(op.type)
    if handler is not None:
        handler(ctx, i, op)
        return
    from . import op_specs  # noqa: F401  (installs the spec table)
    from ..ops import registry
    rule_name = registry.get_sharding_rule(op.type)
    rule = RULES.get(rule_name) if rule_name else None
    if rule is None and op.type.startswith("__"):
        # structural/pass-owned ops not in the table above: replicated
        # outputs, no coverage debt (they are this repo's own emissions)
        _set_all_outputs(ctx, op, ())
        return
    if rule is None:
        if op.type not in ctx._warned_rules:
            ctx._warned_rules.add(op.type)
            ctx.emit("unknown_sharding_rule", "warning",
                     f"op type {op.type!r} declares no sharding rule "
                     "(analysis/op_specs.py): outputs assumed replicated, "
                     "cost prediction may under-count", i, op.type)
        _set_all_outputs(ctx, op, ())
        return
    rule(ctx, i, op)


# ---------------------------------------------------------------------------
# seeding + the propagation walk
# ---------------------------------------------------------------------------

def _seed_specs(ctx) -> None:
    plan = ctx.plan
    block = ctx.block
    zero_specs = dict(getattr(ctx.program, "_zero_state_specs", None) or {})
    # feeds shard their batch dim over the plan's batch axes (DistConfig
    # default: ("dp",)) when the batch divides the axis product
    batch_axes = tuple(a for a in plan.batch_axes if plan.axis(a) > 1)
    batch_size = 1
    for a in batch_axes:
        batch_size *= plan.axis(a)
    for b in ctx.program.blocks:
        for v in b.vars.values():
            if v.is_data:
                spec = [None] * max(len(v.shape), 1)
                d0 = int(v.shape[0]) if v.shape else -1
                if d0 < 0:
                    d0 = plan.batch or 0
                if batch_axes and (d0 == 0 or d0 % batch_size == 0) \
                        and len(v.shape) > 0:
                    spec[0] = batch_axes if len(batch_axes) > 1 \
                        else batch_axes[0]
                ctx.set_spec(v.name, tuple(spec))
    for name, ax in zero_specs.items():
        v = block.find_var_recursive(name)
        shape = tuple(v.shape) if v is not None else None
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        ok = shape is not None and len(shape) >= len(axes)
        for d, a in zip(shape or (), axes):
            if a is not None and (int(d) <= 0
                                  or int(d) % plan.axis(a) != 0):
                ok = False
        ctx.set_spec(name, axes if ok else ())
    rules = plan.param_rules
    for b in ctx.program.blocks:
        for v in b.vars.values():
            if not v.persistable or v.name in ctx.specs:
                continue
            if rules is None:
                ctx.set_spec(v.name, ())
                continue
            spec = tuple(rules.spec_for(v.name, tuple(v.shape)))
            fixed = []
            for i, d in enumerate(v.shape):
                ax = spec[i] if i < len(spec) else None
                if ax is None:
                    fixed.append(None)
                    continue
                size = plan.axis(ax) if isinstance(ax, str) else \
                    int(np.prod([plan.axis(a) for a in ax]))
                fixed.append(ax if size > 1 and int(d) % size == 0
                             else None)
            ctx.set_spec(v.name, tuple(fixed))


def propagate_sharding(program, plan: PlanPoint) -> PropagationResult:
    """Walk the global block op-by-op inferring a ShardSpec for every var
    under `plan`; returns specs + findings + collective events. Pure
    metadata — no trace, no compile."""
    ctx = _Ctx(program, program.global_block(), plan)
    _seed_specs(ctx)
    for i, op in enumerate(ctx.block.ops):
        _propagate_op(ctx, i, op)
    return PropagationResult(specs=ctx.specs, findings=ctx.findings,
                             events=ctx.events)


# ---------------------------------------------------------------------------
# plan checking: fallback matrix + illegal compositions
# ---------------------------------------------------------------------------

def _selected_rows_vars(program) -> List[str]:
    return sorted(v.name for b in program.blocks for v in b.vars.values()
                  if getattr(v, "_is_selected_rows", False))


def _cross_batch_sites(program) -> List[Tuple[int, str]]:
    """(op_index, op_type) of cross-batch ops in the global block,
    INCLUDING ops fused into __segment__/__layer_scan__ bodies (a hidden
    cross-batch op shards just as wrongly as a top-level one)."""
    from . import op_specs
    table = op_specs.cross_batch_ops()

    def walk(attrs):
        for od in attrs.get("sub_ops") or ():
            yield od.get("type")
            yield from walk(od.get("attrs", {}))
        fwd = attrs.get("fwd_attrs")
        if isinstance(fwd, dict):
            yield from walk(fwd)

    sites = []
    seen = set()
    for i, op in enumerate(program.global_block().ops):
        types = [op.type] + list(walk(op.attrs))
        for t in types:
            if t in table and (i, t) not in seen:
                seen.add((i, t))
                sites.append((i, t))
    return sites


def plan_mode(program, plan: PlanPoint) -> str:
    """The execution path this (program, mesh) point takes, mirroring
    `zero.plan_manual_dp`'s structural decision statically:
    "manual" (bucketed shard_map over dp), "gspmd", or "single"."""
    if plan.ndev <= 1:
        return "single"
    if getattr(program, "_grad_buckets", None) is None:
        return "gspmd"
    if plan.dp <= 1 or not plan.dp_pure:
        return "gspmd"
    if getattr(program, "_microbatch_k", 0) and program._microbatch_k > 1:
        return "gspmd"
    if _cross_batch_sites(program):
        return "gspmd"
    if _selected_rows_vars(program):
        return "gspmd"
    if plan.batch is not None and plan.batch % plan.dp != 0:
        return "gspmd"
    return "manual"


def check_plan(program, plan: PlanPoint, strict: bool = False,
               prop: Optional[PropagationResult] = None) -> List[Finding]:
    """Static coherence/affordability lint for one plan point. Emits:

    * `illegal_plan` (error): compositions that cannot run as asked —
      ZeRO stage-3 storage on a mesh with a tensor/sequence/pipeline axis
      (stage-3 flat-shards parameter storage over dp; a second sharding
      axis over the same storage has no lowering — fleet refuses to BUILD
      it, and a planner must prune the point without building).
    * `manual_dp_fallback`: every structural cause that would silently
      drop the manual-dp path at run time, naming the offending op/var
      and the `executor.zero_manual_fallbacks.<cause>` counter it
      predicts. Warnings by default (the program still runs via GSPMD);
      `strict=True` promotes them to errors — the planner's "this plan
      point does not run the way it claims" rejection.
    * the propagation findings (spec conflicts, implicit reshards,
      unknown rules).
    """
    findings: List[Finding] = []
    meta = getattr(program, "_grad_buckets", None) or {}
    stage = int(meta.get("stage", 0) or 0)
    sev = "error" if strict else "warning"

    non_dp = sorted(a for a in plan.mesh_axes
                    if a != "dp" and plan.axis(a) > 1)
    if stage >= 3 and non_dp:
        findings.append(Finding(
            check="illegal_plan", severity="error",
            message=f"sharding_stage=3 flat-shards parameter storage over "
                    f"dp and cannot compose with a "
                    f"{'/'.join(non_dp)} mesh axis (stage3+"
                    f"{non_dp[0]}): prune this plan point"))

    # the fallback matrix applies to any dp-pure plan: a BUCKETED program
    # hits the runtime counters verbatim; an unbucketed one never even
    # attempts the manual path — same structural cause, same warning
    wants_manual = plan.dp > 1 and plan.dp_pure
    if wants_manual:
        from .op_specs import cross_batch_cause
        for i, t in _cross_batch_sites(program):
            cause = cross_batch_cause(t)
            findings.append(Finding(
                check="manual_dp_fallback", severity=sev,
                message=f"op {t!r} couples examples across the global "
                        f"batch: the manual-dp shard_map path declines "
                        f"this program at run time (counter "
                        f"{FALLBACK_COUNTERS[cause]}); it runs via GSPMD "
                        "instead", op_index=i, op_type=t))
        for name in _selected_rows_vars(program):
            findings.append(Finding(
                check="manual_dp_fallback", severity=sev,
                message=f"var {name!r} carries SelectedRows (sparse) "
                        f"gradients: the manual-dp path declines at run "
                        f"time (counter "
                        f"{FALLBACK_COUNTERS['selected_rows']})",
                var=name))
        if getattr(program, "_microbatch_k", 0) \
                and program._microbatch_k > 1:
            findings.append(Finding(
                check="manual_dp_fallback", severity=sev,
                message=f"microbatched (pipeline) program: manual dp "
                        f"declines at run time (counter "
                        f"{FALLBACK_COUNTERS['pipeline']})"))
        if plan.batch is not None and plan.batch % plan.dp != 0:
            findings.append(Finding(
                check="manual_dp_fallback", severity=sev,
                message=f"global batch {plan.batch} is not divisible by "
                        f"dp={plan.dp}: nothing shards, the step runs "
                        f"replicated via GSPMD (counter "
                        f"{FALLBACK_COUNTERS['indivisible_batch']})"))
        for b in getattr(program, "_zero_buckets", None) or ():
            if b["padded"] % plan.dp != 0:
                findings.append(Finding(
                    check="manual_dp_fallback", severity=sev,
                    message=f"flat bucket padding {b['padded']} is not "
                            f"divisible by dp={plan.dp}: state stays "
                            f"replicated and the update runs full-width "
                            f"(counter "
                            f"{FALLBACK_COUNTERS['indivisible_padding']})"))
    elif meta and plan.dp > 1 and not plan.dp_pure:
        findings.append(Finding(
            check="manual_dp_fallback", severity="warning",
            message=f"bucketed program on a mixed mesh "
                    f"({plan.describe()}): the bucket pipeline runs via "
                    f"GSPMD, not shard_map (counter "
                    f"{FALLBACK_COUNTERS['mixed_mesh']})"))

    if prop is None:
        prop = propagate_sharding(program, plan)
    findings.extend(prop.findings)
    return findings
