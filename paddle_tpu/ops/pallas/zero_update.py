"""Fused flat-bucket optimizer-update kernel (Pallas, TPU).

The `__zero_update__` body (parallel/zero.py) runs the shard-local
parameter update through the per-op registry rules (ops/optimizer_ops.py)
over one flat `[padded]` bucket (or a stacked `[L, padded]` bucket under
@LAYERS rolling). Those rules are correct but XLA materializes each
moment read/write as its own HBM round trip — adam touches p, g, m1, m2
plus three outputs, so a bucket makes ~7 passes over HBM for an update
that is pure elementwise arithmetic. This kernel fuses the whole update:
one grid walk over the bucket, every tensor read once, every output
written once — the TPU-native analog of the reference's
`operators/fused/` + xbyak JIT optimizer fusions (SURVEY.md §2.4).

Parity contract: the kernel mirrors the registry rules' dense branches
EXPRESSION FOR EXPRESSION (same op order, same astype placements, same
python-float constants). Everything is elementwise with scalar
broadcasts — no contractions, so nothing may be reassociated.
tests/test_pallas_kernels.py pins the fused result bit-identical to the
jitted rule at every ZeRO stage in interpret mode on the CPU, for
launches of ONE block: there XLA folds the interpreter's grid loop away
and compiles the body as the same fusion the rule is. A launch of
several blocks keeps the loop, XLA compiles its body on its own, and
ParamOut then differs from the rule in the last bit (so it did before
the re-tiling). What Mosaic does on the chip is recorded by
chip_smoke.py's `kernels` leg.
Scalar prologues that the rules compute on [1]-shaped inputs (adam's
bias-corrected lr_t) stay OUTSIDE the kernel, computed with the
identical jnp expression, and ride into the kernel through SMEM, where
the body reads them as scalars (Mosaic loads nothing wider from SMEM).

Tiling: the tensors are walked as what they are, flat 1-D runs, in
blocks of 128 Ki elements — 512 KiB per f32 operand, so adam's four
inputs and three outputs, double-buffered, hold 7 MiB of the 16 MiB
scoped VMEM. No reshape, no padding: a 2-D view of a flat array is a
relayout pass on a TPU unless its rows are exactly 128 wide, and a run
that is no multiple of 128 (ZeRO buckets pad to 64, a dp-way shard to
64/dp) would need a padding copy on top; a 1-D block takes any length
and Pallas masks the ragged last block.

SelectedRows grads and op types without a fused body fall back to the
registry rule at the call site (parallel/zero.py keeps the dispatch).
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

FUSED_OPS = ("sgd", "momentum", "adam", "adamw")

_BLOCK = 128 * 1024   # elements per operand and grid step


def opt_kernel_enabled() -> bool:
    """The training A/B toggle: PADDLE_TPU_PALLAS_OPT=1 (bench arm /
    env) or FLAGS_pallas_opt (programmatic). Read at trace time."""
    if os.environ.get("PADDLE_TPU_PALLAS_OPT", "") == "1":
        return True
    try:
        from ...flags import flag
        return bool(flag("FLAGS_pallas_opt"))
    except Exception:
        return False


def supports(op_type: str, ins) -> bool:
    """True when the fused kernel covers this update: a FUSED_OPS op with
    a dense floating grad (SelectedRows stays on the registry rule)."""
    if op_type not in FUSED_OPS:
        return False
    from ..sparse_grad import is_selected_rows
    g = ins["Grad"][0]
    if is_selected_rows(g):
        return False
    return jnp.issubdtype(g.dtype, jnp.floating)


# --- per-op fused bodies -----------------------------------------------
# Each mirrors the dense branch of the matching ops/optimizer_ops.py rule
# exactly; refs arrive as (scalars..., inputs..., outputs...).

def _sgd_kernel(lr_ref, p_ref, g_ref, po_ref):
    p, g, lr = p_ref[...], g_ref[...], lr_ref[0]
    po_ref[...] = p - lr.astype(p.dtype) * g.astype(p.dtype)


def _momentum_kernel(lr_ref, p_ref, g_ref, v_ref, po_ref, vo_ref, *,
                     mu, use_nesterov, l2_decay):
    p, g, v, lr = p_ref[...], g_ref[...], v_ref[...], lr_ref[0]
    if l2_decay:
        g = g + l2_decay * p
    v_out = mu * v + g
    if use_nesterov:
        p_out = p - lr * (g + mu * v_out)
    else:
        p_out = p - lr * v_out
    po_ref[...] = p_out.astype(p.dtype)
    vo_ref[...] = v_out


def _adam_kernel(lrt_ref, lr_ref, p_ref, g_ref, m1_ref, m2_ref,
                 po_ref, m1o_ref, m2o_ref, *, b1, b2, eps, decay_coeff):
    """adam and (decay_coeff set) adamw. lrt_ref carries the
    bias-corrected lr_t precomputed outside with the rule's own
    expression; lr_ref the raw lr for adamw's decoupled decay."""
    p, g = p_ref[...], g_ref[...]
    m1, m2 = m1_ref[...], m2_ref[...]
    gf = g.astype(m1.dtype)
    m1_out = b1 * m1 + (1 - b1) * gf
    m2_out = b2 * m2 + (1 - b2) * jnp.square(gf)
    lr_t = lrt_ref[0]
    p_out = p - (lr_t * m1_out / (jnp.sqrt(m2_out) + eps)).astype(p.dtype)
    if decay_coeff is not None:
        lr = lr_ref[0]
        p_out = p_out - (lr * decay_coeff * p).astype(p.dtype)
    po_ref[...] = p_out
    m1o_ref[...] = m1_out
    m2o_ref[...] = m2_out


def _run_fused(kernel, name, scalars, tensors, out_dtypes, interpret):
    """Launch an elementwise kernel over same-shape tensors: [1]-shaped
    scalars through SMEM, tensors flattened and walked in _BLOCK-element
    blocks over a 1-D grid."""
    shape = tensors[0].shape
    n = math.prod(int(d) for d in shape)
    # a block equal to the whole (short) array needs no alignment
    block = min(n, _BLOCK)
    tspec = pl.BlockSpec((block,), lambda i: (i,))
    outs = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, block),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)
                  for _ in scalars] + [tspec for _ in tensors],
        out_specs=[tspec for _ in out_dtypes],
        out_shape=[jax.ShapeDtypeStruct((n,), dt) for dt in out_dtypes],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret_mode() if interpret is None else interpret,
        name=name,
    )(*scalars, *[t.reshape(n) for t in tensors])
    return [o.reshape(shape) for o in outs]


def fused_flat_update(op_type: str, ins, attrs, interpret=None):
    """Fused replacement for `registry.get(op_type).lower(...)` on dense
    flat buckets. Same ins/attrs contract, same output dict (including
    the Beta*Pow advances computed with the rule's own scalar expressions).

    Accepts [S] flat and [L, S] stacked (@LAYERS) buckets — the update
    is elementwise, so the kernel walks either layout as one flat run.
    """
    p, g = ins["Param"][0], ins["Grad"][0]
    lr = ins["LearningRate"][0]
    if op_type == "sgd":
        (p_out,) = _run_fused(_sgd_kernel, "zero_update_sgd", [lr], [p, g],
                              [p.dtype], interpret)
        return {"ParamOut": [p_out]}
    if op_type == "momentum":
        v = ins["Velocity"][0]
        rd = attrs.get("regularization_coeff", 0.0)
        if attrs.get("regularization_method", "") != "l2_decay":
            rd = 0.0
        kern = functools.partial(
            _momentum_kernel, mu=attrs.get("mu", 0.9),
            use_nesterov=bool(attrs.get("use_nesterov", False)),
            l2_decay=rd)
        p_out, v_out = _run_fused(kern, "zero_update_momentum", [lr],
                                  [p, g, v], [p.dtype, v.dtype], interpret)
        return {"ParamOut": [p_out], "VelocityOut": [v_out]}
    if op_type in ("adam", "adamw"):
        m1, m2 = ins["Moment1"][0], ins["Moment2"][0]
        b1p, b2p = ins["Beta1Pow"][0], ins["Beta2Pow"][0]
        b1 = attrs.get("beta1", 0.9)
        b2 = attrs.get("beta2", 0.999)
        eps = attrs.get("epsilon", 1e-8)
        decay_coeff = None
        if op_type == "adamw" and attrs.get("with_decay", True):
            decay_coeff = attrs.get("coeff", 0.01)
        # the rule's scalar prologue, verbatim, outside the kernel
        lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
        kern = functools.partial(_adam_kernel, b1=b1, b2=b2, eps=eps,
                                 decay_coeff=decay_coeff)
        p_out, m1_out, m2_out = _run_fused(
            kern, f"zero_update_{op_type}", [lr_t, lr], [p, g, m1, m2],
            [p.dtype, m1.dtype, m2.dtype], interpret)
        return {"ParamOut": [p_out], "Moment1Out": [m1_out],
                "Moment2Out": [m2_out],
                "Beta1PowOut": [b1p * b1], "Beta2PowOut": [b2p * b2]}
    raise ValueError(f"no fused body for op type {op_type!r}")
