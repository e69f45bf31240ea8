"""Flash attention (Pallas, TPU) — forward AND backward kernels.

Replaces the reference's fused CUDA attention (fused/multihead_matmul_op.cu,
math/bert_encoder_functor.cu) with online-softmax tiled kernels: Q blocks
stay resident in VMEM while K/V stream through, so the S×S score matrix never
touches HBM — in either direction.

Forward emits the per-row logsumexp (lse) residual; backward runs two
blockwise kernels (FlashAttention-2 style):
  * dq kernel  — grid over q blocks; streams K/V, accumulates
    dq += ds @ K with ds = P ∘ (dP - delta), P = exp(S - lse).
  * dkdv kernel — grid over k blocks; streams Q/dO/O, accumulates
    dv += Pᵀ @ dO and dk += dsᵀ @ Q.
delta = rowsum(dO ∘ O) is computed in-kernel from resident blocks, so no
extra residual tensor is materialized. lse is stored broadcast along a
128-lane trailing dim (the Mosaic-safe layout).

Two ways to the backward kernels. `jax.vjp(flash_attention)` (custom_vjp):
the forward kernel runs in the vjp's forward pass and hands out/lse to the
backward. `flash_attention(..., return_lse=True)` + `flash_attention_bwd`:
the caller keeps out and lse from a forward launch it already made and runs
the two backward kernels alone (the `fused_attention` op's grad rule,
ops/attention.py: one forward kernel per layer in a train step).

An additive mask rides into all three kernels (the reference handles padded
batches in-kernel too — bert_encoder_functor.cu applies the mask inside the
fused softmax). The mask is normalized to [Bm, Rm, S] where Bm encodes how
heads map onto it (batch-broadcast / head-broadcast / per-(b,h)) and
Rm ∈ {1, S} — a key-padding mask [B,1,1,S] stays O(B·S) in HBM, never
expanded per head or per query row.

Layout. The grid is (batch*heads, blocks) and the non-gridded operand is
fully resident per head (fine up to S~8k at hd 64-128 in 16MB VMEM; longer
sequences use the ring path in parallel/ring_attention.py). Where a head's
[S, hd] lies in HBM is the launch's `layout`, and only the BlockSpecs' index
maps know it:
  * "bhsd": q [B, nh, S, hd], a head is a leading index of the flattened
    [B*nh, S, hd]: what a caller has who transposed its projection.
  * "bshd": q [B, S, nh, hd], which is the projection's own [B, S, nh*hd]:
    a head is a LANE block of a row, block (block_q, hd) at (b, i, h), K / V
    resident as (S, hd) at lane block h // group. All three kernels read
    q, k, v, out, dout there and write out, dq, dk, dv there, so no
    transpose stands around them in either direction. Mosaic takes a lane
    block that is a multiple of 128 wide, so `rows_layout_fits` admits hd
    (and v's width) a multiple of 128 as they are, and hd 64 in PAIRS: a
    grid step takes two neighbouring heads, one 128-lane block
    (`_stack_pair`: the pair's rows are stacked, head A's over head B's,
    each with the other's lanes zeroed, so one contraction over 128 lanes
    gives both heads' scores as [2*block_q, block_k], ONE softmax body
    runs over them, and the products that contract the stacked rows, dk
    and dv, leave each head's sum in its own lanes). A head's dropout
    pattern is keyed on its own index in either layout; lse is [B*nh, S]
    in both.

Grouped KV heads: k and v may be [B, nkv, S, hd] with nkv dividing nh.
They stay at nkv heads in HBM; a query head reaches its KV head through
the BlockSpec's index map (h -> h // group), and the dkdv kernel's grid
gets a group axis that sums the group's query heads into one float32
dK / dV, resident for the KV head. A causal `window` w (a query at i sees
keys i-w+1..i) bounds the loops of all three kernels from both sides, so a
windowed layer visits the blocks its band meets and no others. With
nkv == nh and no window the three kernels trace to the jaxpr
tests/test_mellum.py pins, which has no trace of either.

Which blocks are masked. A causal kernel's inner loop is cut by what a mask
can do to a block (`_loop_ranges`: from the block sizes, `window` and the
grid index alone), the ranges run in ascending order so that every sum keeps
its order: the blocks the diagonal crosses and, with a window, the blocks
its trailing edge crosses run the whole body (`edge`); the blocks between
them, where every (query, key) pair is visible (56 of a head's 72 visits at
S = 4096 with blocks of 256 x 512, 240 of 272 at 8192), run the same body
with no positions built, compared or selected on, and, where no additive
mask and no selection rides in, without the guards that are the identity on
finite scores. A launch that is not causal has one range and its mask is
data, as before. So that cutting a loop costs nothing, the kernels' large
sums (the output's, dq, dk, dv) are VMEM scratch, written in place block by
block, and not loop carries (what a loop carries is copied where it ends),
and a range whose length is known and short, the diagonal's one or two
blocks, is written out without a loop (`_run_ranges`).

A selection: `select` [B, S, S] int8, 1 where query t attends key s (a
learned indexer's choice, ops/sparse_index.py), causal, shared by every
head of a row and so stored once a row: the kernels read the [block_q,
block_k] tile of it beside each score tile and put -inf where it is 0. It is
no additive mask (a float [1, 1, S, S] of it would be four times the bytes
and read once a head) and takes its own path through `flash_attention`
(`_flash_selected`), so that without it nothing here traces differently.
The blocks above the diagonal are skipped as in any causal layer; inside
the triangle a learned selection leaves no block empty, so none is skipped
(ROADMAP A). `selected_probs_sum` is a fourth kernel: the head-summed
probabilities on the selected pairs, which the indexer's loss takes as its
target, from q, k, the forward's lse and the selection, one [block_q, S]
float32 tile resident while the heads go by; no [B, nh, S, S] array exists.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode
from ..registry import keep_under_recompute

_LANES = 128  # Mosaic lane width; lse stored broadcast over it


def _env_block(name: str, default: int) -> int:
    """Env-sweepable block size; must be a positive multiple of 128."""
    raw = os.environ.get(name, "")
    if not raw:
        return default
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r} is not an integer")
    if v < _LANES or v % _LANES:
        raise ValueError(
            f"{name}={v} must be a multiple of {_LANES} and >= {_LANES}")
    return v


# sweepable on hardware without a rebuild (docs/perf_notes.md block sweep)
DEFAULT_BLOCK_Q = _env_block("PADDLE_TPU_FLASH_BLOCK_Q", 256)
DEFAULT_BLOCK_K = _env_block("PADDLE_TPU_FLASH_BLOCK_K", 512)

# odd constants for the counter-based dropout hash (murmur3 fmix32 mixers)
_H1 = 0x85EB_CA6B
_H2 = 0xC2B2_AE35
_H3 = 0x9E37_79B9


def _keep_mask(seed, head, q_off, k_off, block_q, block_k, rate,
               pair=False):
    """Deterministic elementwise keep-mask for attention dropout.

    Counter-based: bit (q_pos, k_pos) of head `head` depends only on
    (seed, head, q_pos, k_pos) — NOT on block geometry — so the forward
    kernel and both backward kernels regenerate identical masks even though
    they tile the score matrix differently. Plain uint32 ops (wrap-around
    multiply + murmur3 finalizer) so it runs under Mosaic and in interpret
    mode alike; pltpu.prng_* has no CPU lowering in this jax.

    `pair`: [2 * block_q, block_k], the masks of heads 2 * head and
    2 * head + 1 one over the other (`_stack_pair`), each what that head
    draws alone.
    """
    qp = _q_positions(q_off, block_q, block_k, pair).astype(jnp.uint32)
    kp = _k_positions(k_off, qp.shape[0], block_k).astype(jnp.uint32)

    def heads():
        if not pair:
            return head.astype(jnp.uint32)
        return head.astype(jnp.uint32) * jnp.uint32(2) + (
            jax.lax.broadcasted_iota(jnp.int32, (2 * block_q, 1), 0)
            >= block_q).astype(jnp.uint32)

    x = (qp * jnp.uint32(_H1)) ^ (kp * jnp.uint32(_H2)) \
        ^ (seed.astype(jnp.uint32) + heads() * jnp.uint32(_H3))
    x ^= x >> 16
    x *= jnp.uint32(_H1)
    x ^= x >> 13
    x *= jnp.uint32(_H2)
    x ^= x >> 16
    thresh = jnp.uint32(min(int(round(rate * 2.0 ** 32)), 2 ** 32 - 1))
    return x >= thresh  # P(keep) = 1 - rate


def _pick_block(s: int, preferred: int) -> int:
    """Largest multiple of 128 that divides s and is <= preferred.

    The grid uses floor division, so a block that doesn't divide s would
    silently leave tail rows unwritten — reject such shapes up front.
    """
    if s % _LANES != 0:
        raise ValueError(
            f"flash_attention requires seq_len % 128 == 0, got {s}")
    b = min(preferred, s)
    b -= b % _LANES
    while s % b != 0:
        b -= _LANES
    return b


def _lanes(d: int) -> int:
    """Lanes a last dimension of `d` occupies in VMEM."""
    return -(-d // _LANES) * _LANES


def _mask_bytes(mask) -> int:
    if mask is None:
        return 0
    return mask.shape[1] * mask.shape[2] * mask.dtype.itemsize


def _compiler_params(resident_bytes: int,
                     semantics=("parallel", "parallel")):
    """Both grid axes parallel (the grouped dkdv kernel's two inner axes
    accumulate and are "arbitrary"). The operands resident per head (K and V
    of a q-grid kernel; Q, dO, O and the lane-broadcast lse of the k-grid
    one) are double-buffered; where they outgrow Mosaic's default 16 MiB of
    scoped VMEM (S = 4096 at 192/128 wide: 16.3 MiB in the dkdv kernel)
    the limit is raised to what they need, inside the chip's 128 MiB.
    Shapes under the default compile exactly as before."""
    need = 2 * resident_bytes + (6 << 20)
    extra = {}
    if need > (16 << 20):
        extra["vmem_limit_bytes"] = min(need, 100 << 20)
    return pltpu.CompilerParams(dimension_semantics=semantics, **extra)


# mask_mode: how the (batch*head) grid index maps to the mask's leading dim.
#   "1"  -> mask shared by every head            (Bm == 1)
#   "b"  -> one mask per batch row, heads share  (Bm == B,    idx = h // nh)
#   "h"  -> one mask per head, batches share     (Bm == nh,   idx = h %  nh)
#   "bh" -> distinct per (batch, head)           (Bm == B*nh, idx = h)
# `nh` is the grid's steps a batch row: the heads, or their pairs (which
# take the first two modes only: `rows_layout_fits`).
def _mask_bidx(mask_mode, nh):
    if mask_mode == "1":
        return lambda h: 0
    if mask_mode == "b":
        return lambda h: h // nh
    if mask_mode == "h":
        return lambda h: h % nh
    return lambda h: h


def _mask_block(mask_ref, q_start, block_q, k_start, block_k):
    """[rows, block_k] additive-bias tile; rows broadcasts when the mask has
    no query-row structure (key-padding case)."""
    cols = pl.ds(k_start, block_k)
    if mask_ref.shape[0] == 1:
        return mask_ref[:, cols]                       # [1, block_k]
    return mask_ref[pl.ds(q_start, block_q), cols]     # [block_q, block_k]


def _visible(q_pos, k_pos, window):
    """Causal visibility of key positions from query positions; with a
    window, the last `window` keys up to the query's own."""
    seen = q_pos >= k_pos
    if window is not None:
        seen = seen & (q_pos - k_pos < window)
    return seen


def _q_positions(q_off, block_q, block_k, pair=False):
    """Query positions of a [block_q, block_k] score tile, int32; in a
    pair's stacked [2 * block_q, block_k] the lower half repeats the
    upper's."""
    rows = 2 * block_q if pair else block_q
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0)
    if pair:
        row = jnp.where(row >= block_q, row - block_q, row)
    return q_off + row


def _k_positions(k_off, rows, block_k):
    """Key positions of a [rows, block_k] score tile, int32."""
    return k_off + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 1)


# the head width that shares a 128-lane block with its neighbour
_PAIR = 64


def _stack_pair(x):
    """[r, 128], two 64-wide heads side by side -> [2r, 128]: head A's rows
    over head B's, each with the other head's lanes zeroed. A contraction
    over all 128 lanes is then each head's own over its 64 (a contraction
    of 64 fills half the MXU's depth anyway), and a product that contracts
    the 2r stacked rows leaves each head's result in its own lanes."""
    first = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) < _PAIR
    zero = jnp.zeros_like(x)
    return jnp.concatenate([jnp.where(first, x, zero),
                            jnp.where(first, zero, x)], axis=0)


def _unstack_pair(x):
    """[2r, 128] -> [r, 128]: head A's lanes of the upper rows beside head
    B's of the lower (the other lanes of each hold a product with the
    other head's values, which nothing reads)."""
    r = x.shape[0] // 2
    first = jax.lax.broadcasted_iota(jnp.int32, (r, x.shape[1]), 1) < _PAIR
    return jnp.where(first, x[:r], x[r:])


def _twice(tile, pair):
    """A [block_q, n] tile that both heads of a pair share (a mask's rows),
    once for each half of the stacked rows; a [1, n] tile broadcasts as it
    is."""
    if not pair or tile.shape[0] == 1:
        return tile
    return jnp.concatenate([tile, tile], axis=0)


def _lse_column(lse_ref, rows, pair):
    """[r, 1] of a lane-broadcast lse block [S or block_q, 128]; of a
    pair's [2, S or block_q, 128] head A's rows over head B's, [2r, 1]."""
    if not pair:
        return lse_ref[rows, :1]
    return jnp.concatenate([lse_ref[0, rows, :1], lse_ref[1, rows, :1]],
                           axis=0)


def _loop_ranges(idx, block_q, block_k, seq_len, causal, window,
                 over_q=False):
    """The inner loop of a kernel as [(start, stop, edge, length), ...], in
    the order the blocks are visited. `idx` is the grid's block: a q block
    whose loop goes over k blocks, or with `over_q` a k block whose loop
    goes over q blocks (the dkdv kernel). A range with `edge` holds the
    blocks that the diagonal or a window's trailing edge
    (q_pos - k_pos < window) crosses: a mask can change a score there.
    In the others (interior) every pair is visible, `_visible` is all
    true. Blocks with no visible pair are in no range. Without `causal`
    no block is provably either: one range, the whole loop, `edge` (which
    compares no positions there). `length` is stop - start where every
    `idx` gives the same (the diagonal's blocks where one block size
    divides the other: a q block of 256 lies in one k block of 512, a k
    block of 512 under two q blocks), else None. Integer arithmetic on
    `idx`, traced (`program_id`) or numpy (the counters, the tests); a
    range that is empty for every `idx` (no window) is left out."""
    xp = jnp if isinstance(idx, jax.Array) else np
    nest = block_k % block_q == 0 or block_q % block_k == 0
    if over_q:
        n = seq_len // block_q
        if not causal:
            return [(0, n, True, n)]
        k_start, k_end = idx * block_k, (idx + 1) * block_k
        # q blocks strictly before this k block see nothing: start at the
        # first q block whose rows reach k_start
        start = k_start // block_q
        if window is None:
            stop = n
        else:
            # the last query that sees this block's last key is window - 1 on
            stop = xp.minimum(n, (k_end - 1 + window - 1) // block_q + 1)
        # past the diagonal from the first q block that starts at k_end on
        lo = xp.minimum((k_end + block_q - 1) // block_q, stop)
        diagonal = (start, lo, True,
                    max(block_k // block_q, 1) if nest else None)
        if window is None:
            return [diagonal, (lo, stop, False, None)]
        # inside the band while the q block's last row still sees k_start
        hi = xp.maximum(xp.minimum((k_start + window) // block_q, stop), lo)
        return [diagonal, (lo, hi, False, None), (hi, stop, True, None)]
    n = seq_len // block_k
    if not causal:
        return [(0, n, True, n)]
    q_start, q_end = idx * block_q, (idx + 1) * block_q
    # only the k blocks that intersect the causal triangle
    stop = xp.minimum(n, (q_end + block_k - 1) // block_k)
    # before the diagonal while the k block ends at or before q_start
    hi = q_start // block_k
    diagonal = (hi, stop, True, max(block_q // block_k, 1) if nest else None)
    if window is None:
        return [(0, hi, False, None), diagonal]
    # the first k block the q block's first row sees
    first = xp.maximum(q_start - (window - 1), 0) // block_k
    # inside the band from the first k block the q block's last row sees
    # whole
    lo = xp.minimum(xp.maximum(q_end - window + block_k - 1, 0) // block_k,
                    hi)
    return [(first, lo, True, None), (lo, hi, False, None), diagonal]


# the most blocks of a range of known length that are written out one by
# one; longer ranges and ranges whose length the grid index decides loop
_UNROLL = 2


def _run_ranges(ranges, body, carry):
    """`carry = body(block, carry, edge)` over the ranges in turn: the
    blocks in the order one loop would take. A range of known, short length
    is written out block by block (a loop's set-up and drain cost as much
    as part of a block, and the diagonal is one or two); the others are one
    `fori_loop` each over the body built for the range's kind. The kernels
    keep their large sums in VMEM scratch and carry at most a column or
    two: what a loop carries is copied where the loop ends, and the range
    is cut in two or three."""
    for start, stop, edge, length in ranges:
        if length is not None and length <= _UNROLL:
            for t in range(length):
                carry = body(start + t, carry, edge=edge)
        else:
            carry = jax.lax.fori_loop(
                start, stop, functools.partial(body, edge=edge), carry)
    return carry


def causal_block_counts(seq_len, window=None, block_q=None, block_k=None):
    """(interior, edge): the (q block, k block) pairs one head of a causal
    forward launch visits, of each kind."""
    bq = _pick_block(seq_len, block_q or DEFAULT_BLOCK_Q)
    bk = _pick_block(seq_len, block_k or DEFAULT_BLOCK_K)
    counts = {False: 0, True: 0}
    for start, stop, edge, _ in _loop_ranges(np.arange(seq_len // bq), bq,
                                             bk, seq_len, True, window):
        counts[edge] += int(np.sum(stop - start))
    return counts[False], counts[True]


def rows_layout_fits(hd, hdv, nh, nkv, mask_shape=None, select=False):
    """Whether the kernels take q [B, S, nh, hd] and k, v [B, S, nkv, ...]
    as they lie (layout "bshd"), a static fact of the shapes: widths that
    are whole 128-lane blocks, or 64-wide heads in pairs: equal, even head
    counts, no mask with a head axis (`mask_shape`: the additive mask's,
    [B or 1, nh or 1, S or 1, S] where it has four axes) and no selection,
    which the pair's one body does not take yet."""
    if hd % _LANES == 0 and hdv % _LANES == 0:
        return True
    per_head_mask = (mask_shape is not None and len(mask_shape) == 4
                     and mask_shape[1] != 1)
    return (hd == hdv == _PAIR and nh == nkv and nh % 2 == 0
            and not per_head_mask and not select)


def _div(step, n):
    """step // n of a grid index, which is never negative: `lax.div`, not
    `//`, whose floor Mosaic lowers through a traced sign helper (a third
    of a launch's lowering time went into its index maps' `//` and `%`)."""
    return jax.lax.div(step, jnp.int32(n))


class _Geometry:
    """Where the heads of a launch's operands lie, from their shapes and
    the layout: the sizes, the arrays flattened to the three axes a
    BlockSpec indexes, and the index maps. `steps` grid steps a batch row
    take `pack` query heads each (2 for a pair, else 1); `kv_steps` the
    same for the KV heads."""

    def __init__(self, q, k, v, rows):
        self.rows = rows
        if rows:
            self.b, self.s, self.nh, self.hd = q.shape
            self.nkv = k.shape[2]
        else:
            self.b, self.nh, self.s, self.hd = q.shape
            self.nkv = k.shape[1]
        self.hdv = v.shape[-1]
        self.group = self.nh // self.nkv
        self.pack = 2 if rows and self.hd == _PAIR else 1
        self.steps = self.nh // self.pack
        self.kv_steps = self.nkv // self.pack

    @property
    def pair(self):
        return self.pack == 2

    def flat(self, t):
        """[B, nh, S, w] -> [B*nh, S, w]; with rows [B, S, nh, w] ->
        [B, S, nh*w]: no data moves."""
        if self.rows:
            return t.reshape(t.shape[0], t.shape[1], -1)
        return t.reshape((-1,) + t.shape[2:])

    def flat_shape(self, heads, width):
        if self.rows:
            return (self.b, self.s, heads * width)
        return (self.b * heads, self.s, width)

    def unflat(self, t, heads):
        if self.rows:
            return t.reshape(self.b, self.s, heads, -1)
        return t.reshape(self.b, heads, self.s, -1)

    def at(self, step, block, kv=False):
        """Block index of grid step `step` (batch * steps a row) at row
        block `block`: a leading index, or with rows a lane block."""
        if not self.rows:
            return (step, block, 0)
        per = self.kv_steps if kv else self.steps
        return (_div(step, per), block, jax.lax.rem(step, jnp.int32(per)))

    def kv_step(self, step):
        """The KV heads' step a query step reads."""
        return step if self.group == 1 else _div(step, self.group)

    def lse_spec(self, rows, index):
        """BlockSpec of the lane-broadcast lse [B*nh, S, 128]: `rows` of
        one head, or of a pair's two."""
        return pl.BlockSpec((2 if self.pair else None, rows, _LANES), index)


def _selected(s, sel_tile):
    """Scores with -inf where the selection's int8 tile is 0."""
    return jnp.where(sel_tile.astype(jnp.int32) != 0, s, -jnp.inf)


def _flash_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, *rest, scale, causal,
                      dropout, block_k, seq_len, has_mask, window=None,
                      has_select=False, pair=False):
    # q_ref: [block_q, hd]; k_ref: [S, hd]; v_ref: [S, hd_v];
    # o_ref: [block_q, hd_v]; hd_v may differ from hd (latent attention:
    # q and k 192 wide, v and the output 128)
    # lse_ref: [block_q, 128] (row value broadcast along lanes)
    # pair: the refs hold two 64-wide heads side by side in their 128
    # lanes, lse_ref is [2, block_q, 128]; the body below runs once over
    # the pair's stacked rows (`_stack_pair`), [2 * block_q, ...]
    # mask_ref (if present): [1 or block_q, S] additive bias
    # sel_ref (if present): [block_q, S] int8, this q block's rows of the
    # row's selection
    sel_ref = None
    if has_select:
        sel_ref, *rest = rest
    mask_ref = None
    if has_mask:
        mask_ref, *rest = rest
    o_ref, lse_ref, acc_ref = rest
    block_q = q_ref.shape[0]
    head = pl.program_id(0)
    q_idx = pl.program_id(1)
    # MXU operands stay in the input dtype (bf16 under AMP — v5e runs bf16
    # matmuls ~4x f32); accumulation is f32 via preferred_element_type, and
    # the scale multiplies the f32 scores AFTER the dot
    q = _stack_pair(q_ref[:]) if pair else q_ref[:]
    rows = q.shape[0]
    # a bias or a selection can empty a row of a block no position masks
    guarded = has_mask or has_select

    # the output's sum is VMEM scratch, written in place block by block
    # (`_run_ranges`); the running maximum and normalizer, a column each,
    # are carried
    m0 = jnp.full((rows, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((rows, 1), jnp.float32)
    acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)

    def body(kb, carry, edge):
        m_prev, l_prev = carry
        k = k_ref[pl.ds(kb * block_k, block_k), :]
        v = v_ref[pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if mask_ref is not None:
            # the q-grid BlockSpec already delivered THIS q block's rows,
            # so the row offset here is 0, not q_idx * block_q
            s = s + _twice(_mask_block(mask_ref, 0, block_q, kb * block_k,
                                       block_k), pair).astype(jnp.float32)
        if causal and edge:
            q_pos = _q_positions(q_idx * block_q, block_q, block_k, pair)
            k_pos = _k_positions(kb * block_k, rows, block_k)
            s = jnp.where(_visible(q_pos, k_pos, window), s, -jnp.inf)
        if sel_ref is not None:
            s = _selected(s, sel_ref[:, pl.ds(kb * block_k, block_k)])
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        if edge or guarded:
            # guard -inf rows (fully-masked): exp(-inf - -inf) -> use safe
            # sub
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe)
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            alpha = jnp.where(jnp.isfinite(m_prev),
                              jnp.exp(m_prev - m_safe), 0.0)
        else:
            # every score finite, so m_new is: exp(-inf - m_new) is the 0
            # an empty carry takes
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        if dropout > 0.0:
            # drop AFTER the normalizer accumulates: out = dropout(P) @ V
            # with P the true softmax — matches upscale_in_train semantics
            keep = _keep_mask(seed_ref[0], head, q_idx * block_q,
                              kb * block_k, block_q, block_k, dropout, pair)
            p_acc = jnp.where(keep, p / (1.0 - dropout), 0.0)
        else:
            p_acc = p
        # probs ride the MXU in the value dtype (f32 accumulate)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p_acc.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new

    m, l = _run_ranges(
        _loop_ranges(q_idx, block_q, block_k, seq_len, causal, window),
        body, (m0, l0))
    out = acc_ref[:] / jnp.maximum(l, 1e-30)
    o_ref[:] = (_unstack_pair(out) if pair else out).astype(o_ref.dtype)
    lse = jnp.where(jnp.isfinite(m), m + jnp.log(jnp.maximum(l, 1e-30)),
                    -jnp.inf)
    if pair:
        lse = lse.reshape(2, block_q, 1)
    lse_ref[:] = jnp.broadcast_to(lse, lse_ref.shape)


def _mask_spec_qgrid(mask, bq, mask_mode, nh):
    """BlockSpec for the mask under a (batch*head, g.at) grid (`nh`
    steps a batch row)."""
    bidx = _mask_bidx(mask_mode, nh)
    bm, rm, s = mask.shape
    if rm == 1:
        return pl.BlockSpec((None, 1, s), lambda h, i: (bidx(h), 0, 0))
    return pl.BlockSpec((None, bq, s), lambda h, i: (bidx(h), i, 0))


def _mask_spec_kgrid(mask, bk, mask_mode, nh):
    """BlockSpec for the mask under a (batch*head, k_block) grid: this k
    block's columns, all query rows resident."""
    bidx = _mask_bidx(mask_mode, nh)
    bm, rm, s = mask.shape
    return pl.BlockSpec((None, rm, bk), lambda h, j: (bidx(h), 0, j))


def _select_bytes(select, rows: int, cols: int) -> int:
    return 0 if select is None else rows * cols


def _flash_fwd(q, k, v, seed, mask, scale, causal, dropout, block_q, block_k,
               mask_mode, window=None, select=None, rows=False):
    g = _Geometry(q, k, v, rows)
    b, nh, s, hd, hdv, pack = g.b, g.nh, g.s, g.hd, g.hdv, g.pack
    bq = _pick_block(s, block_q)
    bk = _pick_block(s, block_k)
    has_mask = mask is not None
    kernel = functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                               dropout=dropout, block_k=bk, seq_len=s,
                               has_mask=has_mask, window=window)
    if select is not None:
        kernel = functools.partial(kernel, has_select=True)
    if g.pair:
        kernel = functools.partial(kernel, pair=True)

    def kv_whole(h, i):
        return g.at(g.kv_step(h), 0, kv=True)

    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((None, bq, pack * hd), g.at),
        pl.BlockSpec((None, s, pack * hd), kv_whole),
        pl.BlockSpec((None, s, pack * hdv), kv_whole),
    ]
    operands = [seed, g.flat(q), g.flat(k), g.flat(v)]
    if select is not None:
        # this q block's rows of the row's selection, whatever the head
        in_specs.append(pl.BlockSpec((None, bq, s),
                                     lambda h, i: (h // nh, i, 0)))
        operands.append(select)
    if has_mask:
        in_specs.append(_mask_spec_qgrid(mask, bq, mask_mode, g.steps))
        operands.append(mask)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * g.steps, s // bq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, bq, pack * hdv), g.at),
            g.lse_spec(bq, lambda h, i: (h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(g.flat_shape(nh, hdv), q.dtype),
            jax.ShapeDtypeStruct((b * nh, s, _LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((pack * bq, pack * hdv), jnp.float32)],
        compiler_params=_compiler_params(
            s * (_lanes(pack * hd) + _lanes(pack * hdv)) * q.dtype.itemsize
            + _mask_bytes(mask) + _select_bytes(select, bq, s)),
        interpret=interpret_mode(),
        name="flash_attention_fwd",
    )(*operands)
    return g.unflat(out, nh), lse


def _flash_bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, o_ref,
                         lse_ref, *rest, scale, causal, dropout, block_k,
                         seq_len, has_mask, window=None, has_select=False,
                         pair=False):
    # q: [block_q, hd]; do/o: [block_q, hd_v]; k: [S, hd]; v: [S, hd_v];
    # lse: [block_q, 128]; sel_ref (if present): [block_q, S] int8
    # pair: two 64-wide heads in the refs' 128 lanes, lse [2, block_q, 128];
    # q and do are stacked (`_stack_pair`) and the body runs once
    sel_ref = None
    if has_select:
        sel_ref, *rest = rest
    mask_ref = None
    if has_mask:
        mask_ref, *rest = rest
    dq_ref, dq_acc = rest
    block_q = q_ref.shape[0]
    head = pl.program_id(0)
    q_idx = pl.program_id(1)
    # MXU operands keep the input dtype (bf16 under AMP), f32 accumulate
    q = q_ref[:]
    do = do_ref[:]
    o = o_ref[:]
    if pair:
        # do's other lanes are zero, so o's need no zeroing for delta
        q, do, o = _stack_pair(q), _stack_pair(do), _twice(o, True)
    lse = _lse_column(lse_ref, slice(None), pair)   # [block_q, 1]
    lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=1, keepdims=True)          # [block_q, 1]

    guarded = has_mask or has_select

    def body(kb, carry, edge):
        k = k_ref[pl.ds(kb * block_k, block_k), :]
        v = v_ref[pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if mask_ref is not None:
            # q-grid BlockSpec already row-tiled the mask: offset 0 here
            s = s + _twice(_mask_block(mask_ref, 0, block_q, kb * block_k,
                                       block_k), pair).astype(jnp.float32)
        if causal and edge:
            q_pos = _q_positions(q_idx * block_q, block_q, block_k, pair)
            k_pos = _k_positions(kb * block_k, q.shape[0], block_k)
            s = jnp.where(_visible(q_pos, k_pos, window), s, -jnp.inf)
        if sel_ref is not None:
            s = _selected(s, sel_ref[:, pl.ds(kb * block_k, block_k)])
        if edge or guarded:
            p = jnp.where(jnp.isfinite(s), jnp.exp(s - lse_safe), 0.0)
        else:
            # every score finite, and so every row's lse
            p = jnp.exp(s - lse_safe)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout > 0.0:
            # d(softmax probs) flows only through kept entries, upscaled;
            # delta = rowsum(dO∘O) already absorbs the mask (O is dropped)
            keep = _keep_mask(seed_ref[0], head, q_idx * block_q,
                              kb * block_k, block_q, block_k, dropout, pair)
            dp = jnp.where(keep, dp / (1.0 - dropout), 0.0)
        ds = p * (dp - delta) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return carry

    # dq's sum is VMEM scratch, nothing is carried
    dq_acc[:] = jnp.zeros(dq_acc.shape, jnp.float32)
    _run_ranges(
        _loop_ranges(q_idx, block_q, block_k, seq_len, causal, window),
        body, 0)
    dq = _unstack_pair(dq_acc[:]) if pair else dq_acc[:]
    dq_ref[:] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkdv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, o_ref,
                           lse_ref, *rest, scale, causal, dropout, block_q,
                           seq_len, has_mask, window=None, group=1,
                           has_select=False, pair=False):
    # k: [block_k, hd]; v: [block_k, hd_v]; q: [S, hd]; do/o: [S, hd_v];
    # lse: [S, 128]
    # pair (group == 1 only): two 64-wide heads in the refs' 128 lanes, lse
    # [2, S, 128]; q and do are stacked a q block (`_stack_pair`), and the
    # products that contract the stacked rows (dv, dk) leave each head's
    # sum in its own lanes of [block_k, 128]
    # mask_ref (if present): [1 or S, block_k] — this k block's columns
    # group == 1: grid (batch*heads, k_block), dk/dv: this k block's rows.
    # group > 1: grid (batch*kv heads, group, k_block); q, do, o, lse are
    # ONE query head's and stay resident while its k blocks go by; dk/dv
    # are the KV head's whole [S, hd] in float32, resident over the two
    # inner axes, and take the sum over the group's query heads.
    # sel_ref (if present): [S, block_k] int8, this k block's columns of
    # the row's selection
    sel_ref = None
    if has_select:
        sel_ref, *rest = rest
    mask_ref = None
    if has_mask:
        mask_ref, *rest = rest
    dk_ref, dv_ref, dk_acc, dv_acc = rest
    block_k = k_ref.shape[0]
    if group == 1:
        head = pl.program_id(0)
        k_idx = pl.program_id(1)
    else:
        member = pl.program_id(1)
        head = pl.program_id(0) * group + member
        k_idx = pl.program_id(2)
    # MXU operands keep the input dtype (bf16 under AMP), f32 accumulate
    k = k_ref[:]
    v = v_ref[:]

    guarded = has_mask or has_select

    def body(qb, carry, edge):
        q = q_ref[pl.ds(qb * block_q, block_q), :]
        do = do_ref[pl.ds(qb * block_q, block_q), :]
        o = o_ref[pl.ds(qb * block_q, block_q), :]
        if pair:
            q, do, o = _stack_pair(q), _stack_pair(do), _twice(o, True)
        lse = _lse_column(lse_ref, pl.ds(qb * block_q, block_q), pair)
        lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=1, keepdims=True)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if mask_ref is not None:
            # columns already sliced by the BlockSpec; rows here
            s = s + _twice(_mask_block(mask_ref, qb * block_q, block_q, 0,
                                       block_k), pair).astype(jnp.float32)
        if causal and edge:
            q_pos = _q_positions(qb * block_q, block_q, block_k, pair)
            k_pos = _k_positions(k_idx * block_k, q.shape[0], block_k)
            s = jnp.where(_visible(q_pos, k_pos, window), s, -jnp.inf)
        if sel_ref is not None:
            s = _selected(s, sel_ref[pl.ds(qb * block_q, block_q), :])
        if edge or guarded:
            p = jnp.where(jnp.isfinite(s), jnp.exp(s - lse_safe), 0.0)
        else:
            # every score finite, and so every row's lse
            p = jnp.exp(s - lse_safe)
        if dropout > 0.0:
            keep = _keep_mask(seed_ref[0], head, qb * block_q,
                              k_idx * block_k, block_q, block_k, dropout,
                              pair)
            p_drop = jnp.where(keep, p / (1.0 - dropout), 0.0)
        else:
            p_drop = p
        # dv += dropout(P)^T @ dO : contract over q rows
        dv_acc[:] += jax.lax.dot_general(
            p_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout > 0.0:
            dp = jnp.where(keep, dp / (1.0 - dropout), 0.0)
        ds = p * (dp - delta) * scale
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return carry

    # dk's and dv's sums are VMEM scratch, nothing is carried
    dk_acc[:] = jnp.zeros(dk_acc.shape, jnp.float32)
    dv_acc[:] = jnp.zeros(dv_acc.shape, jnp.float32)
    _run_ranges(
        _loop_ranges(k_idx, block_q, block_k, seq_len, causal, window,
                     over_q=True),
        body, 0)
    dk, dv = dk_acc[:], dv_acc[:]
    if group == 1:
        dk_ref[:] = dk.astype(dk_ref.dtype)
        dv_ref[:] = dv.astype(dv_ref.dtype)
        return
    rows = pl.ds(pl.multiple_of(k_idx * block_k, block_k), block_k)

    @pl.when(member == 0)
    def _():
        dk_ref[rows, :] = dk
        dv_ref[rows, :] = dv

    @pl.when(member > 0)
    def _():
        dk_ref[rows, :] += dk
        dv_ref[rows, :] += dv


def _flash_bwd(q, k, v, o, lse, do, seed, mask, scale, causal, dropout,
               block_q, block_k, mask_mode, window=None, select=None,
               rows=False):
    g = _Geometry(q, k, v, rows)
    b, nh, nkv, s, hd, hdv = g.b, g.nh, g.nkv, g.s, g.hd, g.hdv
    group, pack = g.group, g.pack
    bq = _pick_block(s, block_q)
    bk = _pick_block(s, block_k)
    wide, wide_v = pack * hd, pack * hdv      # a grid step's lanes
    has_mask = mask is not None

    def q_whole(h, i):
        return g.at(h, 0)

    def kv_block(h, i):
        return g.at(h, i, kv=True)

    def kv_whole(h, i):
        return g.at(g.kv_step(h), 0, kv=True)

    def lse_block(h, i):
        return (h, i, 0)

    def lse_whole(h, i):
        return (h, 0, 0)

    dq_kernel = functools.partial(_flash_bwd_dq_kernel, scale=scale,
                                  causal=causal, dropout=dropout,
                                  block_k=bk, seq_len=s, has_mask=has_mask,
                                  window=window)
    if select is not None:
        dq_kernel = functools.partial(dq_kernel, has_select=True)
    if g.pair:
        dq_kernel = functools.partial(dq_kernel, pair=True)
    dq_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((None, bq, wide), g.at),
        pl.BlockSpec((None, s, wide), kv_whole),
        pl.BlockSpec((None, s, wide_v), kv_whole),
        pl.BlockSpec((None, bq, wide_v), g.at),
        pl.BlockSpec((None, bq, wide_v), g.at),
        g.lse_spec(bq, lse_block),
    ]
    q3, k3, v3, o3, do3 = (g.flat(t) for t in (q, k, v, o, do))
    dq_operands = [seed, q3, k3, v3, do3, o3, lse]
    if select is not None:
        dq_specs.append(pl.BlockSpec((None, bq, s),
                                     lambda h, i: (h // nh, i, 0)))
        dq_operands.append(select)
    if has_mask:
        dq_specs.append(_mask_spec_qgrid(mask, bq, mask_mode, g.steps))
        dq_operands.append(mask)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b * g.steps, s // bq),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((None, bq, wide), g.at),
        out_shape=jax.ShapeDtypeStruct(g.flat_shape(nh, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((pack * bq, wide), jnp.float32)],
        compiler_params=_compiler_params(
            s * (_lanes(wide) + _lanes(wide_v)) * q.dtype.itemsize
            + _mask_bytes(mask) + _select_bytes(select, bq, s)),
        interpret=interpret_mode(),
        name="flash_attention_bwd_dq",
    )(*dq_operands)

    dkdv_kernel = functools.partial(_flash_bwd_dkdv_kernel, scale=scale,
                                    causal=causal, dropout=dropout,
                                    block_q=bq, seq_len=s, has_mask=has_mask,
                                    window=window)
    if select is not None:
        dkdv_kernel = functools.partial(dkdv_kernel, has_select=True)
    if g.pair:
        dkdv_kernel = functools.partial(dkdv_kernel, pair=True)
    # written for the grid of equal head counts, (batch*heads, k_block);
    # `regrid` below turns them to the grouped grid
    dkdv_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((None, s, wide), q_whole),
        pl.BlockSpec((None, bk, wide), kv_block),
        pl.BlockSpec((None, bk, wide_v), kv_block),
        pl.BlockSpec((None, s, wide_v), q_whole),
        pl.BlockSpec((None, s, wide_v), q_whole),
        g.lse_spec(s, lse_whole),
    ]
    dkdv_operands = dq_operands[:7]
    if select is not None:
        # under the grouped grid this spec follows the query head, like q
        dkdv_specs.append(pl.BlockSpec((None, s, bk),
                                       lambda h, i: (h // nh, 0, i)))
        dkdv_operands.append(select)
    if has_mask:
        dkdv_specs.append(_mask_spec_kgrid(mask, bk, mask_mode, g.steps))
        dkdv_operands.append(mask)
    resident = (s * (_lanes(wide) + 2 * _lanes(wide_v)) * q.dtype.itemsize
                + pack * s * _LANES * 4 + _mask_bytes(mask)
                + _select_bytes(select, s, bk))
    dkdv_scratch = [pltpu.VMEM((bk, wide), jnp.float32),
                    pltpu.VMEM((bk, wide_v), jnp.float32)]
    if group == 1:
        dk, dv = pl.pallas_call(
            dkdv_kernel,
            grid=(b * g.steps, s // bk),
            in_specs=dkdv_specs,
            out_specs=[
                pl.BlockSpec((None, bk, wide), kv_block),
                pl.BlockSpec((None, bk, wide_v), kv_block),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(g.flat_shape(nh, hd), k.dtype),
                jax.ShapeDtypeStruct(g.flat_shape(nh, hdv), v.dtype),
            ],
            scratch_shapes=dkdv_scratch,
            compiler_params=_compiler_params(resident),
            interpret=interpret_mode(),
            name="flash_attention_bwd_dkdv",
        )(*dkdv_operands)
    else:
        # grid (batch*kv heads, group, k_block): the group's query heads in
        # turn, each one's q, do, o, lse (and mask) resident while its k
        # blocks go by; k and v follow the KV head; the float32 sums stay in
        # VMEM until the KV head changes
        def regrid(spec, head):
            if spec.index_map is None:          # the seed, in SMEM
                return spec
            return pl.BlockSpec(spec.block_shape, lambda hk, m, j: (
                spec.index_map(head(hk, m), j)))

        def kv_head(hk, m):
            return hk

        def query_head(hk, m):
            return hk * group + m

        def kv_sum(hk, m, j):
            return g.at(hk, 0, kv=True)

        dk, dv = pl.pallas_call(
            functools.partial(dkdv_kernel, group=group),
            grid=(b * nkv, group, s // bk),
            in_specs=[regrid(spec, kv_head if n in (2, 3) else query_head)
                      for n, spec in enumerate(dkdv_specs)],
            out_specs=[
                pl.BlockSpec((None, s, hd), kv_sum),
                pl.BlockSpec((None, s, hdv), kv_sum),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(g.flat_shape(nkv, hd), jnp.float32),
                jax.ShapeDtypeStruct(g.flat_shape(nkv, hdv), jnp.float32),
            ],
            scratch_shapes=dkdv_scratch,
            compiler_params=_compiler_params(
                resident + s * (_lanes(hd) + _lanes(hdv)) * 4,
                ("parallel", "arbitrary", "arbitrary")),
            interpret=interpret_mode(),
            name="flash_attention_bwd_dkdv",
        )(*dkdv_operands)
        dk, dv = dk.astype(k.dtype), dv.astype(v.dtype)

    return g.unflat(dq, nh), g.unflat(dk, nkv), g.unflat(dv, nkv)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _flash(q, k, v, seed, mask, scale, causal, dropout, block_q, block_k,
           mask_mode, window, rows):
    return _flash_fwd(q, k, v, seed, mask, scale, causal, dropout,
                      block_q, block_k, mask_mode, window, rows=rows)


def _kept(out, lse):
    """What a differentiated forward hands its backward, and what a
    recomputed segment keeps so that its backward launches no forward
    kernel (`ops/registry.py` `keep_under_recompute`): `out`, 2 B x S x
    heads x head_dim bytes, and ONE lane of the kernel's lane-broadcast
    `lse`, [B*nh, S] float32, not its 128 copies. Marked before `out` is
    returned as result AND residual: the backward reads what was kept."""
    return keep_under_recompute(out), keep_under_recompute(lse[:, :, 0])


def _widen(lse, dout, rows=False):
    """[B*nh, S] -> the kernels' [B*nh, S, 128], next to the backward
    kernels and not before: the barrier makes the compact values wait for
    dout, or XLA hoists this cheap broadcast to the forward and keeps all
    128 copies alive until here. With `rows` dout [B, S, nh, hd] passes the
    barrier as the [B, S, nh*hd] the kernels read: a value the barrier
    holds is laid out on its own, and XLA gives a 4-D one whose last
    width is 64 an S-minor layout and a copy back (12 a step at s512)."""
    shape = dout.shape
    if rows:
        dout = dout.reshape(shape[0], shape[1], -1)
    lse, dout = jax.lax.optimization_barrier((lse, dout))
    return (jnp.broadcast_to(lse[:, :, None], lse.shape + (_LANES,)),
            dout.reshape(shape))


def _fwd(q, k, v, seed, mask, scale, causal, dropout, block_q, block_k,
         mask_mode, window, rows):
    out, lse = _flash_fwd(q, k, v, seed, mask, scale, causal, dropout,
                          block_q, block_k, mask_mode, window, rows=rows)
    out, lse1 = _kept(out, lse)
    return (out, lse), (q, k, v, seed, mask, out, lse1)


def _bwd(scale, causal, dropout, block_q, block_k, mask_mode, window, rows,
         res, cts):
    q, k, v, seed, mask, o, lse = res
    # lse's own cotangent: it is no result
    lse, do = _widen(lse, cts[0], rows)
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, seed, mask, scale, causal,
                            dropout, block_q, block_k, mask_mode, window,
                            rows=rows)
    dseed = np.zeros(seed.shape, dtype=jax.dtypes.float0)
    # the op registry declares Mask nondiff (ops/attention.py nondiff_slots);
    # a zero cotangent keeps custom_vjp's pytree contract satisfied
    dmask = None if mask is None else jnp.zeros_like(mask)
    return dq, dk, dv, dseed, dmask


_flash.defvjp(_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_selected(q, k, v, seed, select, scale, block_q, block_k, rows):
    """The causal kernels over a selection; no dropout, mask or window."""
    return _flash_fwd(q, k, v, seed, None, scale, True, 0.0, block_q,
                      block_k, None, None, select, rows)


def _selected_fwd(q, k, v, seed, select, scale, block_q, block_k, rows):
    out, lse = _flash_fwd(q, k, v, seed, None, scale, True, 0.0, block_q,
                          block_k, None, None, select, rows)
    out, lse1 = _kept(out, lse)
    return (out, lse), (q, k, v, seed, select, out, lse1)


def _selected_bwd(scale, block_q, block_k, rows, res, cts):
    q, k, v, seed, select, o, lse = res
    lse, do = _widen(lse, cts[0], rows)
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, seed, None, scale, True,
                            0.0, block_q, block_k, None, None, select, rows)
    # the selection is a choice, not a number: nothing flows into it
    return (dq, dk, dv, np.zeros(seed.shape, jax.dtypes.float0),
            np.zeros(select.shape, jax.dtypes.float0))


_flash_selected.defvjp(_selected_fwd, _selected_bwd)


def _probs_sum_kernel(q_ref, k_ref, lse_ref, sel_ref, out_ref, *, scale,
                      block_k, heads):
    # q: [block_q, hd] of ONE query head; k: [S, hd] of its KV head; lse:
    # [block_q, 128]; sel: [block_q, S] int8; out: [block_q, S] float32,
    # resident while the heads (the grid's last axis) go by
    block_q = q_ref.shape[0]
    q_idx = pl.program_id(1)
    head = pl.program_id(2)

    @pl.when(head == 0)
    def _():
        out_ref[:] = jnp.zeros(out_ref.shape, out_ref.dtype)

    q = q_ref[:]
    lse = lse_ref[:, :1]
    lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)

    def body(kb, _, edge):
        cols = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
        s = jax.lax.dot_general(q, k_ref[cols, :], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if edge:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        s = _selected(s, sel_ref[:, cols])
        # the selection can empty a row of any block: the guard stays
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - lse_safe), 0.0)
        out_ref[:, cols] += p * (1.0 / heads)
        return 0

    # the k blocks that meet the causal triangle; the rest stays zero
    _run_ranges(
        _loop_ranges(q_idx, block_q, block_k, k_ref.shape[0], True, None),
        body, 0)


def selected_probs_sum(q, k, lse, select, scale=None,
                       block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                       layout="bhsd"):
    """The mean over the query heads of the attention probabilities, [B, S,
    S] float32, zero off the selection: q [B, nh, S, hd], k [B, nkv, S, hd]
    (`layout` "bshd": [B, S, nh, hd] and [B, S, nkv, hd], read through the
    index maps the other kernels use),
    `lse` [B*nh, S] the logsumexp `flash_attention(..., select=select,
    return_lse=True)` gave, so each head's row sums to 1 over its selected
    keys. Each head's scores are taken once more, a tile at a time; the sum
    over heads stays in VMEM. No gradient is defined: it is a target, and
    the caller stops the gradient of its operands."""
    g = _Geometry(q, k, k, _rows(layout, q, k, k, select=True))
    b, nh, s, hd = g.b, g.nh, g.s, g.hd
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    bq = _pick_block(s, block_q)
    bk = _pick_block(s, block_k)
    lse = jnp.broadcast_to(lse[:, :, None], lse.shape + (_LANES,))
    return pl.pallas_call(
        functools.partial(_probs_sum_kernel, scale=scale, block_k=bk,
                          heads=nh),
        grid=(b, s // bq, nh),
        in_specs=[
            pl.BlockSpec((None, bq, hd), lambda r, i, h: g.at(r * nh + h, i)),
            pl.BlockSpec((None, s, hd), lambda r, i, h: g.at(
                g.kv_step(r * nh + h), 0, kv=True)),
            pl.BlockSpec((None, bq, _LANES),
                         lambda r, i, h: (r * nh + h, i, 0)),
            pl.BlockSpec((None, bq, s), lambda r, i, h: (r, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, s), lambda r, i, h: (r, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.float32),
        compiler_params=_compiler_params(
            s * _lanes(hd) * q.dtype.itemsize + bq * s * 5
            + bq * _LANES * 4, ("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(),
        name="selected_probs_sum",
    )(g.flat(q), g.flat(k), lse, select)


def _normalize_mask(mask, b, nh, s):
    """Additive mask of any shape broadcastable to [B, nh, S, S] (with the
    query dim allowed to be 1) → ([Bm, Rm, S], mask_mode). Key-padding
    masks [B,1,1,S] stay O(B·S); ALiBi-style [1,nh,S,S] stays O(nh·S²)."""
    mask = jnp.asarray(mask)
    if not jnp.issubdtype(mask.dtype, jnp.floating):
        # int/bool additive masks would poison the bwd cotangent pytree
        mask = mask.astype(jnp.float32)
    while mask.ndim < 4:
        mask = mask[None]
    if mask.ndim != 4:
        raise ValueError(f"mask rank must be <= 4, got {mask.shape}")
    mb, mh, mq, mk = mask.shape
    if mk != s or mb not in (1, b) or mh not in (1, nh) or mq not in (1, s):
        raise ValueError(
            f"mask {mask.shape} not broadcastable to attention "
            f"[{b},{nh},{s},{s}]")
    if mh == 1:
        mode = "1" if mb == 1 else "b"
        return mask[:, 0], mode
    if mb == 1:
        return mask[0], "h"
    return mask.reshape(b * nh, mq, s), "bh"


def _check_select(q, select, causal, dropout, mask, window, rows):
    b, s = q.shape[0], q.shape[1 if rows else 2]
    if not causal or dropout or mask is not None or window is not None:
        raise ValueError("flash_attention: a selection goes with causal "
                         "attention alone (no dropout, mask or window)")
    if select.shape != (b, s, s) or select.dtype != jnp.int8:
        raise ValueError(f"flash_attention: select must be int8 "
                         f"[{b}, {s}, {s}], got {select.dtype}"
                         f"{tuple(select.shape)}")


def _rows(layout, q, k, v, mask=None, select=False):
    """Whether `layout` is "bshd"; held to what the kernels take there."""
    if layout == "bhsd":
        return False
    if layout != "bshd":
        raise ValueError(f"flash_attention: unknown layout {layout!r}")
    if not rows_layout_fits(q.shape[-1], v.shape[-1], q.shape[2], k.shape[2],
                            None if mask is None else jnp.shape(mask),
                            select):
        raise ValueError(
            f"flash_attention: layout \"bshd\" takes head widths that are "
            f"multiples of 128, or 64 on equal, even head counts without a "
            f"per-head mask or a selection; got q{tuple(q.shape)} "
            f"k{tuple(k.shape)} v{tuple(v.shape)}")
    return True


def _kernel_args(q, k, v, scale, dropout, seed, mask, causal, window, rows):
    """(scale, seed, mask, mask_mode) as all three kernels take them."""
    heads = 2 if rows else 1            # the axis the heads lie on
    if k.shape[:3] != v.shape[:3] or q.shape[heads] % k.shape[heads]:
        raise ValueError(
            f"flash_attention: q{tuple(q.shape)} needs k and v of equal "
            f"head counts that divide its own, got k{tuple(k.shape)} "
            f"v{tuple(v.shape)}")
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"flash_attention: window={window} needs causal=True and "
            f"window >= 1")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if dropout > 0.0 and seed is None:
        raise ValueError("flash_attention dropout requires a seed")
    if not (q.dtype == k.dtype == v.dtype):
        # the kernels feed MXU dots in the operand dtype; mixed inputs
        # would crash inside the backward kernels mid-training
        raise ValueError(
            f"flash_attention requires matching q/k/v dtypes, got "
            f"{q.dtype}/{k.dtype}/{v.dtype}")
    seed = jnp.asarray(0 if seed is None else seed, jnp.int32).reshape((1,))
    mask_mode = None
    if mask is not None:
        mask, mask_mode = _normalize_mask(mask, q.shape[0], q.shape[heads],
                                          q.shape[3 - heads])
    return scale, seed, mask, mask_mode


def flash_attention(q, k, v, scale=None, causal=False,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    dropout=0.0, seed=None, mask=None, return_lse=False,
                    window=None, select=None, layout="bhsd"):
    """Tiled attention of q [B, nh, S, hd] over k, v [B, nkv, S, hd], nkv
    dividing nh (query head h reads KV head h // (nh / nkv)); with `layout`
    "bshd" q is [B, S, nh, hd], k and v [B, S, nkv, hd] and the result
    [B, S, nh, hd_v]: a projection's rows as they lie (the module docstring
    says which shapes the kernels take so; others raise). With `causal`
    a `window` w lets a query at i see keys i-w+1..i only. `dropout` drops
    post-softmax probs with an in-kernel counter-based mask keyed on `seed`
    (traced int32 scalar/array ok); `select` [B, S, S] int8 keeps, for every
    head of a row, the pairs where it is 1 (causal attention only);
    `mask` is an additive bias broadcastable to [B, nh, S(or 1), S] applied
    to the scaled scores inside all three kernels. With `return_lse` the
    result is (out, lse): lse is the forward kernel's per-row logsumexp,
    float32 [B*nh, S], which `flash_attention_bwd` takes beside out. The
    kernels hold it lane-broadcast ([B*nh, S, 128], 128 copies of each
    value); what is kept from the forward to the backward is one of them.
    Differentiated by JAX, the forward kernel runs inside the vjp's forward
    pass."""
    rows = _rows(layout, q, k, v, mask, select is not None)
    scale, seed, mask, mask_mode = _kernel_args(
        q, k, v, scale, dropout, seed, mask, causal, window, rows)
    if select is not None:
        _check_select(q, select, causal, dropout, mask, window, rows)
        out, lse = _flash_selected(q, k, v, seed, select, scale, block_q,
                                   block_k, rows)
    else:
        out, lse = _flash(q, k, v, seed, mask, scale, causal, float(dropout),
                          block_q, block_k, mask_mode, window, rows)
    return (out, lse[:, :, 0]) if return_lse else out


def flash_attention_bwd(q, k, v, out, lse, dout, scale=None, causal=False,
                        block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                        dropout=0.0, seed=None, mask=None, window=None,
                        select=None, layout="bhsd"):
    """(dq, dk, dv) from the residuals a forward launch already wrote: the
    two backward kernels alone, with the arguments `flash_attention` took
    (`out` and `dout` in q's `layout`, as the gradients are).
    What `jax.vjp(flash_attention)` computes after running the forward
    kernel for `out` and `lse` itself."""
    rows = _rows(layout, q, k, v, mask, select is not None)
    scale, seed, mask, mask_mode = _kernel_args(
        q, k, v, scale, dropout, seed, mask, causal, window, rows)
    lse, dout = _widen(lse, dout, rows)
    if select is not None:
        _check_select(q, select, causal, dropout, mask, window, rows)
    return _flash_bwd(q, k, v, out, lse, dout, seed, mask, scale, causal,
                      float(dropout), block_q, block_k, mask_mode, window,
                      select, rows)
