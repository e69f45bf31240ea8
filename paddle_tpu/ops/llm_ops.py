"""Ops of the decoder LMs after 2020: RMS norm, rotary positions on
interleaved pairs, the gated (SwiGLU) product.

Each is one plain `jax.numpy` lowering that XLA fuses with its
neighbours; gradients come from the generic `__vjp__`. Under AMP the norm
is on the black list (float32), the other two keep the dtype they are
given and compute in float32 inside (amp/auto_cast.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register


@register("rms_norm")
def _rms_norm(ctx, ins, attrs):
    """y = x / sqrt(mean(x^2, last axis) + eps) * scale (Zhang & Sennrich
    2019), statistics in float32."""
    x = ins["X"][0]
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                        + attrs.get("epsilon", 1e-6))
    y = xf * inv
    if ins.get("Scale"):
        y = y * ins["Scale"][0].astype(jnp.float32)
    return {"Y": [y.astype(x.dtype)]}


def rotary_interleaved(x, theta: float, rotary_dim: int):
    """Rotate the LAST `rotary_dim` features of x [..., S, D] by position
    (axis -2, positions 0..S-1): the pairs (2i, 2i+1) turn by
    pos * theta^(-2i / rotary_dim) (Su et al. 2021, the interleaved
    layout). The rest of D passes through."""
    s, d = x.shape[-2], x.shape[-1]
    half = rotary_dim // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0
                            / rotary_dim))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)                    # [S, half]
    rot = x[..., d - rotary_dim:].astype(jnp.float32)
    pairs = rot.reshape(rot.shape[:-1] + (half, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    out = out.reshape(rot.shape).astype(x.dtype)
    if rotary_dim == d:
        return out
    return jnp.concatenate([x[..., :d - rotary_dim], out], axis=-1)


@register("rotary_embedding")
def _rotary_embedding(ctx, ins, attrs):
    x = ins["X"][0]
    return {"Out": [rotary_interleaved(
        x, float(attrs.get("theta", 10000.0)),
        int(attrs.get("rotary_dim", x.shape[-1])))]}


@register("swiglu")
def _swiglu(ctx, ins, attrs):
    """silu(gate) * up (Shazeer 2020), the product in float32."""
    g, u = ins["Gate"][0], ins["Up"][0]
    out = jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
    return {"Out": [out.astype(g.dtype)]}
