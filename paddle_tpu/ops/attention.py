"""Fused attention op.

Reference counterpart: operators/fused/multihead_matmul_op.cu +
math/bert_encoder_functor.cu (hand-written CUDA attention). TPU-native: one
op whose lowering is either (a) the XLA path — two MXU matmuls + fused
softmax, which XLA already schedules well — or (b) a Pallas flash-attention
kernel (ops/pallas/flash_attention.py) when running on real TPU with
supported shapes, cutting HBM traffic for long sequences. The choice is
made from the backend and the static shapes alone (`_route`), once for the
forward and once more, from the same facts, for the op's grad rule: on the
flash route the backward takes the forward launch's `Out` and `Lse` and runs
the two backward kernels; on every other route the rule declines and the
generic `__vjp__` differentiates the lowering (docs/custom_ops.md).

Dtypes. The matmul operands `Q`, `K`, `V` (and, in the backward, the
cotangent of `Out`) are cast at the op's boundary by the AMP rule every
matmul op follows (`amp/auto_cast.py`: the op is white-listed), so under
AMP the flash kernels, the head relayouts around them and the residuals
`Q`, `K`, `V`, `Out` are in the compute dtype (bf16); without AMP they are
what the program built. No lowering here casts them again: `Out` has the
operands' dtype, every dot accumulates float32, and softmax, logsumexp,
the dropout hash, `Mask` and `Lse` are float32 on every route.

Grouped KV heads and a window. `K` and `V` may carry fewer heads than `Q`,
[B, nkv, S, hd] with nkv dividing nh: query head h attends KV head
h // (nh / nkv), read from the operands' shapes. With `causal`, the attr
`window` w lets a query at position i see keys i-w+1..i. The flash route
hands both to the kernels (K and V stay at nkv heads in HBM, dK and dV
leave at nkv heads); the dense route computes the same mathematics over a
[B, nkv, group, S, S] score tensor; the ring / Ulysses routes know one KV
head per query head and no window: K and V are repeated there
(`attention.flash_kv_expanded`), a window raises.

Layout. The attr `layout` says how the inputs arrive: "bhsd" (the default),
Q [B, nh, S, hd] with K and V [B, nkv, S, ...], or "bshd", Q [B, S, nh, hd]
with K and V [B, S, nkv, ...]: a projection's [B, S, heads * hd] cut into
heads by a reshape and nothing else. `Out` leaves in the inputs' layout,
`Lse` is [B, nh, S] in both. On the flash route, where the shapes are ones
the kernels index as lane blocks of a row (`rows_layout_fits`: widths that
are multiples of 128, or 64 on equal, even head counts), a "bshd" op hands
the arrays over as they are and no transpose exists in either direction of
the step (`attention.flash_layout_rows`); everywhere else (the dense and the
ring / Ulysses routes, a width of 192, 64-wide heads on grouped KV heads,
a selection or a per-head mask at 64) the op transposes inside itself and
runs exactly what a "bhsd" op runs (`attention.flash_layout_heads` where
that is the flash kernels), so a builder may always pass "bshd". A static
fact of the shapes like the route, read by the forward and the grad rule
alike.

A selection. `Select` [B, S, S] int8 (1 where query t attends key s; a
learned indexer's, ops/sparse_index.py) is an input of its own, shared by
all heads of a row and stored once a row; with `causal` alone. The flash
route hands it to the kernels tile by tile (counter `attn.sparse_pallas`);
the dense route puts -inf where it is 0 (`attn.sparse_xla`); the ring /
Ulysses routes raise. No gradient reaches it. With the attr `return_target`
the op also gives `Target` [B, S, S] float32: the mean over the query heads
of the probabilities, zero off the selection, what the indexer's loss is
held to: a fourth kernel on the flash route (`selected_probs_sum`, no
[B, nh, S, S] array in HBM), the heads' mean of `probs` on the dense one,
under the scope `attn.index.target`; no gradient passes through it.
"""
from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp

from .registry import keep_under_recompute, register

# counter suffix by the dtype q reached the flash forward in
_OPERAND_TAG = {"bfloat16": "bf16", "float32": "f32"}


def _xla_attention(q, k, v, mask, scale, dropout, key, select=None,
                   want_target=False):
    # q: [B, nh, S, hd]; k, v: [B, nkv, S, hd], nkv dividing nh. Where
    # `group` query heads share a KV head the group is an axis of q and of
    # the scores, and K and V are read as they are. With `want_target` the
    # result is (out, the heads' mean of probs [B, S, S], no gradient)
    b, nh, s, _ = q.shape
    nkv = k.shape[1]
    if nkv != nh:
        scores = jnp.einsum("bngqd,bnkd->bngqk",
                            q.reshape(b, nkv, nh // nkv, s, -1), k,
                            preferred_element_type=jnp.float32) * scale
        scores = scores.reshape(b, nh, s, s)
    else:
        scores = jnp.einsum("bnqd,bnkd->bnqk", q, k,
                            preferred_element_type=jnp.float32) * scale
    if mask is not None:
        scores = scores + mask.astype(scores.dtype)
    if select is not None:
        scores = jnp.where(select[:, None] != 0, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    if want_target:
        with jax.named_scope("attn.index.target"):
            target = jax.lax.stop_gradient(jnp.mean(probs, axis=1))
    if dropout and key is not None:
        from .rng import fast_keep_mask
        keep = fast_keep_mask(key, 1.0 - dropout, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout), 0.0)
    probs = probs.astype(v.dtype)
    if nkv != nh:
        out = jnp.einsum("bngqk,bnkd->bngqd",
                         probs.reshape(b, nkv, nh // nkv, s, s),
                         v).reshape(b, nh, s, v.shape[-1])
    else:
        out = jnp.einsum("bnqk,bnkd->bnqd", probs, v)
    return (out, target) if want_target else out


def _causal_bias(s, window):
    """Additive [1, 1, S, S] bias of a causal layer: -1e9 above the
    diagonal and, with a window, `window` or more below it."""
    tri = jnp.triu(jnp.full((s, s), -1e9, jnp.float32), 1)
    if window is not None:
        pos = jnp.arange(s)
        tri = jnp.where(pos[:, None] - pos[None, :] >= window, -1e9, tri)
    return tri[None, None]


def _derive_seed(key):
    """Squeeze the op's run key to the int32 the counter-based dropout
    masks hash on — ONE derivation shared by the flash and sp paths so
    they draw identical patterns for the same op seed."""
    return jax.random.randint(key, (), jnp.iinfo(jnp.int32).min,
                              jnp.iinfo(jnp.int32).max, dtype=jnp.int32)


def _mask_flashable(mask, q):
    """Additive masks the kernels take in-kernel: any shape broadcastable to
    [B, nh, S(or 1), S]. Anything else (e.g. per-example ragged objects)
    falls back to the dense path."""
    b, nh, s, _ = q.shape
    shp = tuple(getattr(mask, "shape", ()))
    if len(shp) > 4 or not shp:
        return False
    shp = (1,) * (4 - len(shp)) + shp
    return (shp[3] == s and shp[0] in (1, b) and shp[1] in (1, nh)
            and shp[2] in (1, s))


def _use_pallas(q):
    """Static gate for the flash kernels: a TPU backend and a shape they
    tile (q's; `_route` holds v's width, which may differ, to the kernels'
    too). There is no runtime probe and no fallback behind this gate — a
    kernel that Mosaic refuses, or that fails, raises."""
    import os
    if jax.default_backend() != "tpu":
        return False
    b, nh, s, hd = q.shape
    # short sequences: the [B,nh,S,S] score tensor fits XLA's fused softmax
    # comfortably and the dense path WINS (round-4 A/B at S=128: dense
    # 175 ms/step vs flash 230); flash pays off once the S^2 HBM traffic
    # dominates. Crossover set conservatively at 512, env-overridable.
    min_seq = int(os.environ.get("PADDLE_TPU_FLASH_MIN_SEQ", "512"))
    return s >= min_seq and s % 128 == 0 and hd in (64, 128, 192, 256)


def _route(ctx, q, v, mask, attrs):
    """("sp", fn) | ("flash", None) | ("dense", None): which lowering this
    op takes, from static facts alone (attrs, mesh, backend, shapes), so
    the forward and the grad rule cannot disagree. Build-time shape
    inference always reads "dense"."""
    if ctx.is_eval_shape or isinstance(q, jax.ShapeDtypeStruct):
        return "dense", None
    if attrs.get("sequence_parallel"):
        mesh = _current_mesh()
        if mesh is not None and "sp" in mesh.axis_names \
                and mesh.shape["sp"] > 1:
            if attrs.get("window"):
                raise NotImplementedError(
                    "fused_attention: the ring / Ulysses routes have no "
                    "window")
            from ..parallel.ring_attention import (ring_attention,
                                                   ulysses_attention)
            fn = (ulysses_attention
                  if attrs.get("sp_mode") == "ulysses" else ring_attention)
            return "sp", functools.partial(fn, mesh=mesh)
    # q and k share one width, v and the output another (latent attention:
    # 192 and 128)
    if _use_pallas(q) and v.shape[-1] in (64, 128, 256) \
            and (mask is None or _mask_flashable(mask, q)):
        return "flash", None
    return "dense", None


def _unpack(ins, attrs):
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    mask = ins["Mask"][0] if ins.get("Mask") else None
    scale = attrs.get("scale", 1.0 / math.sqrt(q.shape[-1]))
    dropout = attrs.get("dropout", 0.0)
    if attrs.get("is_test", False):
        dropout = 0.0
    return q, k, v, mask, scale, dropout, attrs.get("causal", False)


def _bshd(attrs):
    return attrs.get("layout", "bhsd") == "bshd"


def _swap_heads(t):
    """[B, S, heads, w] <-> [B, heads, S, w]."""
    return jnp.swapaxes(t, 1, 2)


# the shape and dtype a "bshd" input has once head-major
_HeadMajor = collections.namedtuple("_HeadMajor", "shape dtype")


def _facts(t, attrs):
    """`t` as the static gates read it, [B, heads, S, width]: of a "bshd"
    input the shape alone, since the gates must trace no transpose to
    decide (a `ShapeDtypeStruct` stays one: `_route` knows it by type)."""
    if not _bshd(attrs):
        return t
    b, s, heads, width = t.shape
    kind = (jax.ShapeDtypeStruct if isinstance(t, jax.ShapeDtypeStruct)
            else _HeadMajor)
    return kind((b, heads, s, width), t.dtype)


def _kernel_layout(route, q, k, v, mask, attrs, select=None):
    """The layout the flash kernels are launched in: "bshd" where a "bshd"
    op's inputs are ones they take as they lie, else "bhsd" (static facts;
    `q`, `k`, `v` as `_facts` gives them)."""
    from .pallas.flash_attention import rows_layout_fits
    if route == "flash" and _bshd(attrs) and rows_layout_fits(
            q.shape[-1], v.shape[-1], q.shape[1], k.shape[1],
            None if mask is None else mask.shape, select is not None):
        return "bshd"
    return "bhsd"


def _laid_out(outs, moved):
    """`Out` back in a "bshd" op's layout where the op ran head-major
    (`moved`: it transposed its inputs)."""
    if moved:
        outs["Out"] = [_swap_heads(outs["Out"][0])]
    return outs


def _window(attrs):
    """The attr `window`, absent where the layer has none (the layer
    function holds it to `causal`)."""
    return int(attrs.get("window") or 0) or None


def _select(ins, attrs, mask, causal, dropout):
    """The `Select` input, or None; held to what the routes support."""
    if not ins.get("Select"):
        return None
    if not causal or mask is not None or dropout or _window(attrs):
        raise ValueError("fused_attention: Select goes with causal=True "
                         "alone (no Mask, dropout or window)")
    return ins["Select"][0]


def _no_lse(q):
    """`Lse` off the flash route: an empty placeholder that nothing reads
    (the flash forward writes its per-row logsumexp [B, nh, S] there for
    the grad rule)."""
    return jnp.zeros(q.shape[:2] + (0,), jnp.float32)


def _count_causal_blocks(s, window):
    """At trace time, per causal flash forward lowered: the (q block, k
    block) pairs a head visits, by whether a position can mask a score
    there (`edge`) or the kernels run their bare loop body (`interior`)."""
    from .pallas.flash_attention import causal_block_counts
    from ..observability import metrics
    interior, edge = causal_block_counts(s, window)
    metrics.inc("attention.flash_blocks_interior", interior)
    metrics.inc("attention.flash_blocks_edge", edge)


def _count_layout(layout):
    """At trace time, per flash forward lowered: whether the kernels read
    the projection's rows as they lie ("bshd"), or heads a transpose laid
    out (the caller's, or for a "bshd" op this op's own)."""
    from ..observability import metrics
    metrics.inc("attention.flash_layout_rows" if layout == "bshd"
                else "attention.flash_layout_heads")


def _flash_failed(e, q, mask, causal, dropout):
    return RuntimeError(
        f"pallas flash attention failed for q{tuple(q.shape)} "
        f"{q.dtype}, mask "
        f"{None if mask is None else tuple(mask.shape)}, "
        f"causal={causal}, dropout={dropout}: {e}")


def _fused_attention_grad(ctx, ins, attrs, outs, ogs):
    """Grad rule: on the flash route the backward is the two backward
    kernels on the `Out` and `Lse` the forward launch wrote. Every other
    route (and a program built before `Lse` existed) declines, and the
    generic `__vjp__` differentiates the forward lowering as before."""
    q, k, v, mask, scale, dropout, causal = _unpack(ins, attrs)
    dout = (ogs.get("Out") or [None])[0]
    select = _select(ins, attrs, mask, causal, dropout)
    fq, fk, fv = (_facts(t, attrs) for t in (q, k, v))
    route = _route(ctx, fq, fv, mask, attrs)[0]
    if route != "flash" or dout is None \
            or not outs.get("Out") or not outs.get("Lse"):
        return None
    from .pallas.flash_attention import flash_attention_bwd
    from ..observability import metrics
    out, lse = outs["Out"][0], outs["Lse"][0]
    b, nh, s, _ = fq.shape
    seed = _derive_seed(ctx.op_key(attrs)) if dropout else None
    dout = dout.astype(out.dtype)
    layout = _kernel_layout(route, fq, fk, fv, mask, attrs, select)
    moved = _bshd(attrs) and layout == "bhsd"
    if moved:
        q, k, v, out, dout = (_swap_heads(t) for t in (q, k, v, out, dout))
    try:
        grads = flash_attention_bwd(
            q, k, v, out, lse.reshape(b * nh, s), dout,
            scale=scale, causal=causal, dropout=dropout, seed=seed,
            mask=mask, window=_window(attrs), select=select, layout=layout)
    except Exception as e:
        raise _flash_failed(e, q, mask, causal, dropout) from e
    metrics.inc("attention.flash_bwd_residual")
    if moved:
        grads = [_swap_heads(t) for t in grads]
    return dict(zip("QKV", ([t] for t in grads)))


@register("fused_attention", is_random=True,
          nondiff_slots=("Mask", "Select"),
          grad=_fused_attention_grad, residual_slots=("Out", "Lse"))
def _fused_attention(ctx, ins, attrs):
    q, k, v, mask, scale, dropout, causal = _unpack(ins, attrs)
    key = ctx.op_key(attrs) if dropout else None
    fq, fk, fv = (_facts(t, attrs) for t in (q, k, v))
    (b, nh, s, _), nkv = fq.shape, fk.shape[1]
    route, sp_fn = _route(ctx, fq, fv, mask, attrs)
    window = _window(attrs)
    select = _select(ins, attrs, mask, causal, dropout)
    layout = _kernel_layout(route, fq, fk, fv, mask, attrs, select)
    moved = _bshd(attrs) and layout == "bhsd"
    if moved:
        q, k, v = (_swap_heads(t) for t in (q, k, v))
    if select is not None:
        return _laid_out(_selected_attention(
            ctx, q, k, v, select, scale, route,
            bool(attrs.get("return_target")), layout), moved)
    if route == "sp":
        if nkv != nh:
            from ..observability import metrics
            metrics.inc("attention.flash_kv_expanded")
            k, v = (jnp.repeat(t, nh // t.shape[1], axis=1) for t in (k, v))
        sp_seed = _derive_seed(key) if dropout else None
        # key-padding masks + in-body counter dropout ride the ring
        # (round 4; full [S, S] masks still raise — see _check_mask)
        return _laid_out(
            {"Out": [sp_fn(q, k, v, scale=scale, causal=causal,
                           mask=mask, dropout=float(dropout),
                           seed=sp_seed)],
             "Lse": [_no_lse(q)]}, moved)
    if route == "flash":
        from .pallas.flash_attention import flash_attention
        seed = _derive_seed(key) if dropout else None
        try:
            out, lse = flash_attention(q, k, v, scale=scale, causal=causal,
                                       dropout=dropout, seed=seed, mask=mask,
                                       return_lse=True, window=window,
                                       layout=layout)
        except Exception as e:
            raise _flash_failed(e, q, mask, causal, dropout) from e
        from ..observability import metrics
        metrics.inc("attention.flash_operands_"
                    + _OPERAND_TAG.get(q.dtype.name, q.dtype.name))
        metrics.inc("attention.flash_window" if window
                    else "attention.flash_full")
        _count_layout(layout)
        if nkv != nh:
            metrics.inc("attention.flash_kv_grouped")
        if causal:
            _count_causal_blocks(s, window)
        if ctx.in_vjp:
            # the generic __vjp__ (a whole segment under recompute or layer
            # scan) lowers this forward a second time to differentiate it
            metrics.inc("attention.flash_bwd_recomputed")
        return _laid_out({"Out": [out], "Lse": [lse.reshape(b, nh, s)]},
                         moved)
    if causal:
        tri = _causal_bias(s, window)
        mask = tri if mask is None else mask + tri
    return _laid_out(
        {"Out": [_xla_attention(q, k, v, mask, scale, dropout, key)],
         "Lse": [_no_lse(q)]}, moved)


def _selected_attention(ctx, q, k, v, select, scale, route, want_target,
                        layout):
    """`fused_attention` over a selection: {"Out", "Lse"[, "Target"]}; q,
    k, v and `Out` in `layout` (`_kernel_layout`'s)."""
    from ..observability import metrics
    b, nh = q.shape[0], q.shape[2 if layout == "bshd" else 1]
    s = select.shape[1]
    if route == "sp":
        raise NotImplementedError(
            "fused_attention: the ring / Ulysses routes have no selection")
    count = not (ctx.is_eval_shape or ctx.in_vjp)
    if route == "flash":
        from .pallas.flash_attention import (flash_attention,
                                             selected_probs_sum)
        try:
            out, lse = flash_attention(q, k, v, scale=scale, causal=True,
                                       return_lse=True, select=select,
                                       layout=layout)
        except Exception as e:
            raise _flash_failed(e, q, None, True, 0.0) from e
        if count:
            metrics.inc("attn.sparse_pallas")
        _count_layout(layout)
        _count_causal_blocks(s, None)
        if ctx.in_vjp:
            metrics.inc("attention.flash_bwd_recomputed")
        outs = {"Out": [out], "Lse": [lse.reshape(b, nh, s)]}
        if want_target:
            with jax.named_scope("attn.index.target"):
                # a recomputed segment keeps it: every head's scores once
                # more for a [B, S, S] float32 the loss's backward reads
                outs["Target"] = [keep_under_recompute(selected_probs_sum(
                    *jax.lax.stop_gradient((q, k, lse)), select,
                    scale=scale, layout=layout))]
        return outs
    if count and not isinstance(q, jax.ShapeDtypeStruct):
        metrics.inc("attn.sparse_xla")
    got = _xla_attention(q, k, v, _causal_bias(s, None), scale, 0.0, None,
                         select, want_target)
    out, target = got if want_target else (got, None)
    outs = {"Out": [out], "Lse": [_no_lse(q)]}
    if want_target:
        outs["Target"] = [target]
    return outs


def _current_mesh():
    """Mesh for the program being lowered (SPMD attach), else the global."""
    from ..framework import executor as _ex
    if _ex._lowering_programs:
        dist = getattr(_ex._current_lowering_program(), "_dist_config", None)
        if dist is not None:
            return dist.resolve_mesh()
    from ..parallel.mesh import get_mesh
    return get_mesh()
