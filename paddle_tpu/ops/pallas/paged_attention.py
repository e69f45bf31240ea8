"""Fused paged-attention decode kernel (Pallas, TPU).

The jnp oracle (ops/paged_ops.paged_attend) re-materializes every slot's
FULL dense cache view per layer per token — `paged_gather` reshapes the
pool into [B, nh, MB*bs, hd] in HBM before the attention einsums ever run.
Decode is memory-bandwidth-bound, so that gather IS the tokens/s tax
(PagedAttention, Kwon et al. SOSP '23; the kernel design follows the
jax/vLLM TPU formulation).

This kernel fuses gather + score + softmax + context into ONE pallas_call
that walks each slot's page-table row with scalar prefetch:

* grid (B, nh, MB): the page table and positions ride SMEM ahead of the
  body, so the k/v BlockSpec index_map picks each step's POOL BLOCK
  directly — the dense view never exists in HBM;
* blocks past a slot's write frontier (j*bs > pos) clamp their index map
  to the previous block — consecutive identical indices make the Mosaic
  pipeline ELIDE the DMA, so out-of-range blocks cost no HBM traffic —
  and skip the staging store via pl.when;
* block steps only STAGE: each [bs, hd] pool block is stored into this
  slot's [MB*bs, hd] VMEM rows at sublane offset j*bs (a whole number of
  tiles for f32 and bf16 at bs 16). Nothing is ever stored at a lane
  offset — a [1, bs] score slice written at lane j*bs is what Mosaic
  refused in the first version of this kernel;
* the score dot, mask, softmax and context run ONCE, at the last block
  step, over the full-width rows: masked lanes are the oracle's exact
  -inf and the rows never staged are exact zeros, so the full-row
  jax.nn.softmax + context matmul see bit-identical values at
  bit-identical width. The softmax is deliberately the full-row form
  rather than a cross-block online rescale: rescaling reorders the f32
  sums, and the serving contract (docs/serving.md) pins BITWISE parity
  against the oracle in interpret mode — exp/sum over rows whose extra
  lanes are exactly 0.0 is bit-stable, a cross-block alpha-weighted
  accumulation is not. The two VMEM rows cost 2 * max_len * hd * itemsize
  bytes per (slot, head) — 1 MB at max_len 2048 / hd 128 / f32 — well
  inside the 16 MB budget;
* MXU accumulators are f32 (Mosaic takes nothing narrower); a bf16 pool's
  context is rounded to bf16 once, after the dot, as XLA does for the
  oracle's bf16 einsum;
* int8-KV pools are staged through an exact int8->f32 convert and the
  dequantize_abs_max multiplier (scale/127, ops/int8_ops.py) is folded
  to the post-dot position — the form that is bit-stable across XLA
  fusion contexts; see kv_dequant_scale for why the naive per-element
  dequant is not.

Runs under interpret=True on the CPU backend (ops/pallas.interpret_mode)
so the tier-1 parity matrix (tests/test_pallas_kernels.py) pins the
kernel bit-for-bit against paged_attend on every suite run.
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

_INT8_MAX_RANGE = 127.0   # dequantize_abs_max max_range (ops/int8_ops.py)


def decode_kernel_enabled() -> bool:
    """The serving A/B toggle: PADDLE_TPU_PALLAS_DECODE=1 (bench arm /
    env) or FLAGS_pallas_decode (programmatic). Read at engine build /
    trace time — flipping it invalidates nothing already compiled."""
    if os.environ.get("PADDLE_TPU_PALLAS_DECODE", "") == "1":
        return True
    try:
        from ...flags import flag
        return bool(flag("FLAGS_pallas_decode"))
    except Exception:
        return False


def kv_dequant_scale(kv_scale) -> float:
    """The int8-KV dequant multiplier — the dequantize_abs_max math
    (ops/int8_ops.py): payload * scale / 127.

    The int8-KV attention CONTRACT (shared with paged_ops.paged_attend's
    int8 arm) folds this multiplier to the OUTSIDE of both contractions:

        scores = dot(q, int8->f32(K)) * (attn_scale * c)
        ctx    = dot(probs, int8->f32(V)) * c

    rather than dequantizing per element before the dot. int8->f32 is
    exact, so the dot runs over exactly-representable values, and a
    post-dot scalar multiply is XLA's canonical form — the algebraic
    simplifier has nothing to reassociate. The naive per-element form is
    NOT bit-stable across fusion contexts: XLA hoists `dot(q, k * c)` to
    `dot(q, k) * c` when the dequant fuses into the score dot, drifting
    1 ulp between kernel and oracle (and optimization_barrier has no
    Mosaic lowering, so it cannot pin the naive form on real TPU)."""
    return float(kv_scale) / _INT8_MAX_RANGE


def _paged_decode_kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                         k_row, v_row, *, block_size, grid_blocks, scale,
                         kv_scale):
    """One (slot, head, block) grid step.

    pt_ref/pos_ref: SMEM scalar prefetch ([B, MB] / [B] int32);
    q_ref/o_ref [1, hd]; k_ref/v_ref [bs, hd] (this step's pool block);
    scratch k_row/v_row [MB*bs, hd]: this slot's cache rows, persisting
    across the block dimension — pool dtype, or f32 for int8 pools.

    Deferring every contraction to the last step is also what keeps the
    int8 arm bit-stable: a convert feeding a dot in the same fusion
    context lets XLA re-order the contraction (1-ulp drift vs the
    oracle), while a scratch round-trip across grid steps pins the
    converted values before any contraction sees them."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    p = pos_ref[b]
    bs = block_size

    @pl.when(j == 0)
    def _init():
        # rows the walk never stages must be exact zeros: their softmax
        # weight is 0.0, and 0.0 * stale VMEM could be NaN (values) or
        # leave a NaN score under the mask (keys)
        k_row[...] = jnp.zeros_like(k_row)
        v_row[...] = jnp.zeros_like(v_row)

    @pl.when(j * bs <= p)
    def _stage():
        rows = pl.ds(pl.multiple_of(j * bs, bs), bs)
        k_row[rows, :] = k_ref[...].astype(k_row.dtype)
        v_row[rows, :] = v_ref[...].astype(v_row.dtype)

    @pl.when(j == grid_blocks - 1)
    def _finish():
        q = q_ref[...]
        k = k_row[...]                                         # [K, hd]
        v = v_row[...]
        # int8: the dequant multiplier rides the post-dot scales,
        # mirroring paged_attend's folded int8 arm (kv_dequant_scale)
        c = 1.0 if kv_scale is None else kv_scale / _INT8_MAX_RANGE
        # same contraction as the oracle's score einsum: f32 accumulate
        s = jnp.einsum("qd,kd->qk", q, k,
                       preferred_element_type=jnp.float32) * (scale * c)
        kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # -inf == the oracle's additive mask at full width: masked lanes
        # contribute exp(-inf)=0 to the softmax sum
        s = jnp.where(kpos <= p, s, -jnp.inf)
        probs = jax.nn.softmax(s, axis=-1)
        # the oracle's context einsum: probs cast to the value dtype
        out = jnp.einsum("qk,kd->qd", probs.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        if kv_scale is not None:
            out = out * c
        o_ref[...] = out.astype(o_ref.dtype)


def fused_paged_attention(q, k_pool, v_pool, page_table, pos, *,
                          block_size: int, layer: int = 0, scale=None,
                          max_blocks=None, kv_scale=None, interpret=None):
    """Fused single-token paged attention.

    q [B, nh, 1, hd]; k_pool/v_pool [L, NB, nh, bs, hd] (float, or int8
    with `kv_scale` set); page_table [B, MB] int32; pos [B] int32.
    Returns the context [B, nh, 1, hd] bit-identical (f32 path) to
    `paged_attend(q, k_pool, v_pool, page_table, pos, ...)`.

    `max_blocks` (static) bounds the page-table WALK — the VMEM rows
    stay full width so the softmax denominators match the oracle at any
    hint, while blocks >= max_blocks are never visited at all."""
    b, nh, one, hd = q.shape
    if one != 1:
        raise ValueError(f"decode kernel takes a single query token, "
                         f"got q {q.shape}")
    mb = page_table.shape[1]
    bs = int(block_size)
    if k_pool.shape[3] != bs:
        raise ValueError(f"pool block dim {k_pool.shape[3]} != "
                         f"block_size {bs}")
    if (kv_scale is None) != (k_pool.dtype != jnp.int8):
        raise ValueError("int8 pools need kv_scale (and only int8 do)")
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    grid_blocks = mb if max_blocks is None else max(1, min(mb,
                                                           int(max_blocks)))
    out_dtype = (jnp.float32 if kv_scale is not None else k_pool.dtype)
    page_table = page_table.astype(jnp.int32)
    pos = pos.astype(jnp.int32)

    def block_idx(bi, hi, ji, pt_ref, pos_ref):
        # clamp the walk to this slot's write frontier: past it the index
        # repeats the frontier block, so the pipeline skips the DMA
        jc = jnp.minimum(ji, pos_ref[bi] // bs)
        return (layer, pt_ref[bi, jc], hi, 0, 0)

    kernel = functools.partial(
        _paged_decode_kernel, block_size=bs, grid_blocks=grid_blocks,
        scale=scale, kv_scale=None if kv_scale is None else float(kv_scale))
    row = pl.BlockSpec((None, None, 1, hd),
                       lambda bi, hi, ji, pt, ps: (bi, hi, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nh, grid_blocks),
        in_specs=[row,
                  pl.BlockSpec((None, None, None, bs, hd), block_idx),
                  pl.BlockSpec((None, None, None, bs, hd), block_idx)],
        out_specs=row,
        scratch_shapes=[pltpu.VMEM((mb * bs, hd), out_dtype),
                        pltpu.VMEM((mb * bs, hd), out_dtype)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, 1, hd), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode() if interpret is None else interpret,
        name="paged_attention_decode",
    )(page_table, pos, q, k_pool, v_pool)
