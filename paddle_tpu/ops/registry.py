"""Op registry: op name -> JAX lowering + static shape inference.

TPU-native replacement for the reference's operator registry & kernel dispatch
(reference: paddle/fluid/framework/op_registry.h:230, operator.cc:1017-1141).
Where the reference selects a (place, dtype, layout, library) kernel at run time,
here each op has ONE lowering — a pure JAX function — and XLA owns code
generation, fusion and layout. Gradients do not need hand-written grad kernels:
`append_backward` emits a generic `__vjp__` op whose lowering calls `jax.vjp`
on the forward lowering (reference grad-op makers: grad_op_desc_maker.h). An
op whose forward already wrote what its backward needs may declare a grad
rule (`register(..., grad=fn, residual_slots=(...))`): the `__vjp__` op then
reads those forward outputs and calls the rule instead (docs/custom_ops.md).

Lowering signature:
    lower(ctx, ins: Dict[slot, List[jax.Array]], attrs: dict)
        -> Dict[slot, List[jax.Array]]

Build-time shape inference runs the lowering under `jax.eval_shape` with a
sentinel substituted for unknown (-1) batch dims, then maps the sentinel back.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Callable, Dict, List, Optional

import jax
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..framework.dtype import convert_dtype

# Sentinel concrete size standing in for -1 dims during build-time inference.
_DYN_SENTINEL = 8191


class LowerCtx:
    """Per-execution context handed to lowerings (rng base key, mesh info)."""

    __slots__ = ("rng_key", "mesh", "is_eval_shape", "in_vjp", "pullbacks")

    def __init__(self, rng_key=None, mesh=None, is_eval_shape=False,
                 in_vjp=False):
        self.rng_key = rng_key
        self.mesh = mesh
        self.is_eval_shape = is_eval_shape
        # True while the generic __vjp__ lowers a forward op AGAIN to
        # differentiate it: a lowering whose repeat XLA cannot merge with
        # the first (a Mosaic kernel) counts itself when it sees this
        self.in_vjp = in_vjp
        # what a forward lowering that differentiated itself left for its
        # __vjp__ op later in the same walk (parallel/transforms.py
        # `__segment__`): {key: (outputs, pullback)}
        self.pullbacks = {}

    def op_key(self, attrs):
        """Deterministic per-op PRNG key: fold the op's stable seed attr into the
        run key. Grad re-execution with the same attrs reproduces the same
        randomness (so dropout masks match between forward and __vjp__)."""
        seed = attrs.get("__rng_seed__", 0)
        return jax.random.fold_in(self.rng_key, seed)


class OpDef:
    def __init__(self, name: str, lower: Callable, infer: Optional[Callable] = None,
                 is_random: bool = False, nondiff_slots=(), stateful_outputs=(),
                 grad: Optional[Callable] = None, residual_slots=()):
        self.name = name
        self.lower = lower
        self.infer = infer          # optional custom infer(block, op)
        self.is_random = is_random  # gets a stable __rng_seed__ attr at build
        self.nondiff_slots = frozenset(nondiff_slots)
        # output slots aliasing an input (e.g. optimizer ParamOut) — excluded
        # from autodiff bookkeeping
        self.stateful_outputs = frozenset(stateful_outputs)
        # grad(ctx, ins, attrs, outs, ogs) -> {input slot: [grad or None]},
        # or None to decline (the generic jax.vjp route then runs). `outs`
        # holds the forward op's outputs of `residual_slots`, which
        # append_backward wires into the __vjp__ op as "FO:<slot>" inputs
        # (the reference's grad-op makers take Out beside X and Out@GRAD)
        self.grad = grad
        self.residual_slots = tuple(residual_slots)


_REGISTRY: Dict[str, OpDef] = {}

# Optional per-op slot/attr metadata consumed by the program verifier
# (paddle_tpu/analysis/verifier.py) and the static sharding/cost analysis
# (analysis/sharding.py, analysis/cost.py). Kept as an opaque side table
# so op modules never pay an import or a construction cost for it;
# populated by paddle_tpu/analysis/op_specs.py (the reference's
# OpProto/OpMaker declarations + auto_parallel SPMD completion rules,
# reduced to what static checking needs). Each spec may carry a
# `sharding` rule name (how var specs propagate through the op) and a
# `cross_batch` flag (the op couples examples across the global batch —
# the manual-dp decline table).
_SPECS: Dict[str, object] = {}


def set_spec(name: str, spec) -> None:
    """Attach verifier metadata (an analysis.op_specs.OpSpec) to an op."""
    _SPECS[name] = spec


def get_spec(name: str):
    return _SPECS.get(name)


def get_sharding_rule(name: str) -> Optional[str]:
    """The op's declared spec-propagation rule name (None = uncovered)."""
    spec = _SPECS.get(name)
    return getattr(spec, "sharding", None)


def register(name: str, *, infer=None, is_random=False, nondiff_slots=(),
             stateful_outputs=(), grad=None, residual_slots=()):
    def deco(fn):
        _REGISTRY[name] = OpDef(name, fn, infer=infer, is_random=is_random,
                                nondiff_slots=nondiff_slots,
                                stateful_outputs=stateful_outputs,
                                grad=grad, residual_slots=residual_slots)
        return fn
    return deco


def get(name: str) -> OpDef:
    if name not in _REGISTRY:
        from ..framework import errors
        raise errors.Unimplemented(
            "op %r is not registered; register a lowering with "
            "paddle_tpu.ops.registry.register (docs/custom_ops.md)", name)
    return _REGISTRY[name]


def has(name: str) -> bool:
    return name in _REGISTRY


def all_ops() -> List[str]:
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# Recomputation: what a checkpointed segment keeps beside its boundary
# ---------------------------------------------------------------------------

# The one name `checkpointed`'s policy saves. An op marks a value with
# `keep_under_recompute` where it makes it: a choice or a probability that
# several kernels were spent on and that is small beside a layer's
# activations (a selection, its target, the flash output, a route's
# indices). The recomputed forward then reads the kept value, and what
# only made it is dead code there.
_KEPT = "kept_under_recompute"

# (count, times) while a checkpointed unit is being lowered (`recomputed`),
# else None: outside one a mark is no equation at all.
_recomputing = None


def checkpointed(fn):
    """`fn` as one recomputed unit: `jax.checkpoint` that saves the unit's
    inputs and the values ops marked inside it, nothing else. Call and
    differentiate the result under `recomputed`."""
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(_KEPT))


@contextlib.contextmanager
def recomputed(count, times=1):
    """Around the lowering and differentiation of a `checkpointed` unit
    (`__segment__`; a `__layer_scan__` with `remat`, whose body runs
    `times` layers): marks take effect, and are counted if `count` (the
    lowering whose residuals the step keeps, once a trace)."""
    global _recomputing
    outer, _recomputing = _recomputing, (count, times)
    try:
        yield
    finally:
        _recomputing = outer


def keep_under_recompute(x):
    """Mark `x` as kept by the checkpointed unit being lowered; `x` itself
    outside one. Mark a value where it is made, before anything reads it:
    a reader of the unmarked twin (a `custom_vjp`'s residual) has it made
    again. Counter `recompute.kept_values`, gauge `recompute.kept_bytes`
    (docs/observability.md)."""
    if _recomputing is None:
        return x
    count, times = _recomputing
    if count:
        from ..framework import executor
        from ..observability import metrics
        nbytes = times * x.size * x.dtype.itemsize
        metrics.inc("recompute.kept_values", times)
        # summed over one walk of a program's ops (one trace of a step)
        walk = executor._lower_table
        if walk is not None:
            walk.kept_bytes += nbytes
            nbytes = walk.kept_bytes
        else:
            nbytes += metrics.get("recompute.kept_bytes")
        metrics.set_gauge("recompute.kept_bytes", nbytes)
    return checkpoint_name(x, _KEPT)


# ---------------------------------------------------------------------------
# Build-time shape/dtype inference (reference: InferShape, shape_inference.h)
# ---------------------------------------------------------------------------

def infer_op(block, op) -> None:
    block.program.bump_version()  # before any early return: compiled caches
    # key on the version, so every structural change must invalidate them
    opdef = _REGISTRY.get(op.type)
    if opdef is None:
        return  # tolerate unregistered ops at build; execution will fail loudly
    if opdef.is_random and "__rng_seed__" not in op.attrs:
        # per-program counter: two identically-built programs draw identical
        # init values under the same paddle.seed (a process-global counter
        # would silently break determinism/loss-parity tests)
        ctr = getattr(block.program, "_rng_op_counter", None)
        if ctr is None:
            # cloned/deserialized programs lack the attr: resume above the
            # highest seed already present so new random ops never collide
            ctr = 1 + max((o.attrs.get("__rng_seed__", 0)
                           for b in block.program.blocks for o in b.ops),
                          default=0)
        op.attrs["__rng_seed__"] = ctr
        block.program._rng_op_counter = ctr + 1
    if opdef.infer is not None:
        opdef.infer(block, op)
        return
    try:
        _generic_infer(block, op, opdef)
    except Exception:
        # Build-time inference is advisory; execution specializes on real
        # shapes. Leave unknown shapes in place rather than failing the build.
        pass


def _generic_infer(block, op, opdef) -> None:
    ins = {}
    for slot, names in op.inputs.items():
        specs = []
        for n in names:
            v = block.var(n)
            shape = tuple(_DYN_SENTINEL if d in (-1, None) else d for d in v.shape)
            specs.append(jax.ShapeDtypeStruct(shape, v.dtype))
        ins[slot] = specs
    def _run(i, key):
        ctx = LowerCtx(rng_key=key, is_eval_shape=True)
        return opdef.lower(ctx, i, op.attrs)

    outs = jax.eval_shape(_run, ins, jax.random.key(0))
    for slot, names in op.outputs.items():
        if slot not in outs:
            continue
        for n, spec in zip(names, outs[slot]):
            if n == "@EMPTY@":
                continue
            v = block.find_var_recursive(n)
            if v is None:
                continue
            v.shape = tuple(-1 if d == _DYN_SENTINEL else int(d)
                            for d in spec.shape)
            v.dtype = convert_dtype(spec.dtype)


# ---------------------------------------------------------------------------
# Generic VJP grad op (replaces per-op grad kernels; reference grad makers)
# ---------------------------------------------------------------------------

def make_vjp_attrs(fwd_op, diff_entries, out_slots_order):
    """diff_entries: list of (slot, index) of forward inputs to differentiate."""
    return {
        "fwd_type": fwd_op.type,
        "fwd_attrs": dict(fwd_op.attrs),
        "fwd_input_slots": {k: len(v) for k, v in fwd_op.inputs.items()},
        "fwd_output_slots": list(out_slots_order),
        "fwd_output_counts": {s: len(fwd_op.outputs.get(s, []))
                              for s in out_slots_order},
        "diff_entries": [list(e) for e in diff_entries],
        "op_role": 1,  # OpRole.Backward
    }


def cotangents(outs, ogs):
    """The cotangents of one slot's forward outputs `outs` from what
    arrived for them, `ogs` (aligned; short, or None, where an output has
    no reader): zeros for those."""
    cts = []
    for j, ref in enumerate(outs):
        if j < len(ogs) and ogs[j] is not None:
            ct = ogs[j]
            # AMP may deliver cotangents in a different float dtype than
            # this op's output (e.g. bf16 grads into an f32 op) — align.
            # TensorArray-valued outputs are (buffer, length) pytrees:
            # align leaf-wise (the length leaf's cotangent is symbolic).
            if isinstance(ref, tuple):
                ct = jax.tree_util.tree_map(
                    lambda c, r: c if c is None
                    or getattr(c, "dtype", None) == r.dtype
                    or not jax.numpy.issubdtype(r.dtype, jax.numpy.floating)
                    else c.astype(r.dtype), tuple(ct), ref)
            elif ct.dtype != ref.dtype:
                ct = ct.astype(ref.dtype)
            cts.append(ct)
        elif isinstance(ref, tuple):
            cts.append(jax.tree_util.tree_map(
                lambda r: jax.numpy.zeros(r.shape, r.dtype), ref))
        else:
            cts.append(jax.numpy.zeros(ref.shape, ref.dtype))
    return cts


def _lower_vjp(ctx, ins, attrs):
    fwd = get(attrs["fwd_type"])
    fwd_attrs = attrs["fwd_attrs"]
    in_slot_counts = attrs["fwd_input_slots"]
    out_slots = attrs["fwd_output_slots"]
    diff = [tuple(e) for e in attrs["diff_entries"]]

    fwd_ins = {slot: list(ins[slot]) for slot in in_slot_counts}
    if fwd.grad is not None:
        # the op's own rule, fed the forward's outputs; None = it declines
        # for this shape/backend and the generic route below runs
        grads = fwd.grad(
            ctx, fwd_ins, fwd_attrs,
            {s: ins[f"FO:{s}"] for s in fwd.residual_slots
             if f"FO:{s}" in ins},
            {s: ins.get(f"OG:{s}", []) for s in out_slots})
        if grads is not None:
            return {f"IG:{s}": [grads[s][i] if (s, i) in diff else None
                                for i in range(in_slot_counts[s])]
                    for s in dict.fromkeys(s for s, _ in diff)}
    primals = [fwd_ins[s][i] for (s, i) in diff]
    relower_ctx = LowerCtx(ctx.rng_key, ctx.mesh, ctx.is_eval_shape,
                           in_vjp=True)

    def f(*diff_vals):
        cur = {s: list(vs) for s, vs in fwd_ins.items()}
        for (s, i), v in zip(diff, diff_vals):
            cur[s][i] = v
        outs = fwd.lower(relower_ctx, cur, fwd_attrs)
        return [v for s in out_slots for v in outs[s]]

    out_flat, vjp_fn = jax.vjp(f, *primals)
    # Cotangents arrive in slot "OG:<slot>", aligned with the forward op's
    # output lists; entries for unused outputs are missing and become zeros.
    cts = []
    idx = 0
    for s in out_slots:
        n_outs = attrs["fwd_output_counts"][s]
        cts += cotangents(out_flat[idx:idx + n_outs], ins.get(f"OG:{s}", []))
        idx += n_outs
    grads = vjp_fn(cts)
    by_slot = {}
    for (s, i), g in zip(diff, grads):
        by_slot.setdefault(s, {})[i] = g
    result = {}
    for s, m in by_slot.items():
        result[f"IG:{s}"] = [m.get(i) for i in range(in_slot_counts[s])]
    return result


def _vjp_infer(block, op):
    """Build-time shapes for grad vars are EXACTLY the forward inputs'
    shapes — never eval_shape the vjp lowering (it would re-trace the
    forward AND its transpose per op at build time; for batch-looping ops
    the dynamic-dim sentinel makes that catastrophically slow)."""
    block.program.bump_version()
    for slot, names in op.outputs.items():
        if not slot.startswith("IG:"):
            continue
        fwd_names = op.inputs.get(slot[3:], [])
        for n, src in zip(names, fwd_names):
            if n == "@EMPTY@" or src == "@EMPTY@":
                continue
            v = block.find_var_recursive(n)
            s = block.find_var_recursive(src)
            if v is not None and s is not None:
                v.shape = tuple(s.shape)
                v.dtype = s.dtype


_REGISTRY["__vjp__"] = OpDef("__vjp__", _lower_vjp, infer=_vjp_infer)
