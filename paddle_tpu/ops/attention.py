"""Fused attention op.

Reference counterpart: operators/fused/multihead_matmul_op.cu +
math/bert_encoder_functor.cu (hand-written CUDA attention). TPU-native: one
op whose lowering is either (a) the XLA path — two MXU matmuls + fused
softmax, which XLA already schedules well — or (b) a Pallas flash-attention
kernel (ops/pallas/flash_attention.py) when running on real TPU with
supported shapes, cutting HBM traffic for long sequences. The choice is
made from the backend and the static shapes alone (`_use_pallas`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .registry import register


def _xla_attention(q, k, v, mask, scale, dropout, key):
    # q,k,v: [B, nh, S, hd]
    scores = jnp.einsum("bnqd,bnkd->bnqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        scores = scores + mask.astype(scores.dtype)
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout and key is not None:
        from .rng import fast_keep_mask
        keep = fast_keep_mask(key, 1.0 - dropout, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout), 0.0)
    probs = probs.astype(v.dtype)
    return jnp.einsum("bnqk,bnkd->bnqd", probs, v)


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
    tile. There is no runtime probe and no fallback behind this gate — a
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
    return s >= min_seq and s % 128 == 0 and hd in (64, 128, 256)


@register("fused_attention", is_random=True, nondiff_slots=("Mask",))
def _fused_attention(ctx, ins, attrs):
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    mask = ins["Mask"][0] if ins.get("Mask") else None
    scale = attrs.get("scale", 1.0 / math.sqrt(q.shape[-1]))
    dropout = attrs.get("dropout", 0.0)
    if attrs.get("is_test", False):
        dropout = 0.0
    key = ctx.op_key(attrs) if dropout else None
    causal = attrs.get("causal", False)
    if attrs.get("sequence_parallel") and not ctx.is_eval_shape \
            and not isinstance(q, jax.ShapeDtypeStruct):
        mesh = _current_mesh()
        if mesh is not None and "sp" in mesh.axis_names \
                and mesh.shape["sp"] > 1:
            from ..parallel.ring_attention import (ring_attention,
                                                   ulysses_attention)
            fn = (ulysses_attention
                  if attrs.get("sp_mode") == "ulysses" else ring_attention)
            sp_seed = _derive_seed(key) if dropout else None
            # key-padding masks + in-body counter dropout ride the ring
            # (round 4; full [S, S] masks still raise — see _check_mask)
            return {"Out": [fn(q, k, v, mesh=mesh, scale=scale,
                               causal=causal, mask=mask,
                               dropout=float(dropout), seed=sp_seed)]}
    if not ctx.is_eval_shape \
            and not isinstance(q, jax.ShapeDtypeStruct) and _use_pallas(q) \
            and (mask is None or _mask_flashable(mask, q)):
        from .pallas.flash_attention import flash_attention
        seed = _derive_seed(key) if dropout else None
        try:
            out = flash_attention(q, k, v, scale=scale, causal=causal,
                                  dropout=dropout, seed=seed, mask=mask)
        except Exception as e:
            raise RuntimeError(
                f"pallas flash attention failed for q{tuple(q.shape)} "
                f"{q.dtype}, mask "
                f"{None if mask is None else tuple(mask.shape)}, "
                f"causal={causal}, dropout={dropout}: {e}") from e
        return {"Out": [out]}
    if causal:
        s = q.shape[2]
        tri = jnp.triu(jnp.full((s, s), -1e9, jnp.float32), 1)[None, None]
        mask = tri if mask is None else mask + tri
    return {"Out": [_xla_attention(q, k, v, mask, scale, dropout, key)]}



def _current_mesh():
    """Mesh for the program being lowered (SPMD attach), else the global."""
    from ..framework import executor as _ex
    if _ex._lowering_programs:
        dist = getattr(_ex._current_lowering_program(), "_dist_config", None)
        if dist is not None:
            return dist.resolve_mesh()
    from ..parallel.mesh import get_mesh
    return get_mesh()
