"""Ops of the decoder LMs after 2020: RMS norm, rotary positions (pairs
interleaved or half-split, frequencies by the default rule or yarn's), the
gated (SwiGLU) product, the squared ReLU, the L2 norm over a head and the
head-wise sigmoid gate.

Each is one plain `jax.numpy` lowering that XLA fuses with its
neighbours; gradients come from the generic `__vjp__`. Under AMP the norm
is on the black list (float32), the others keep the dtype they are
given and compute in float32 inside (amp/auto_cast.py).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

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


def _turned_part(x, rotary_dim: int, start):
    """(first turned feature, the `rotary_dim` turned features of x in
    float32): from `start`, or the LAST ones (None)."""
    start = x.shape[-1] - rotary_dim if start is None else int(start)
    if not 0 <= start <= x.shape[-1] - rotary_dim:
        raise ValueError(
            f"rotary_embedding: rotary_start {start} + rotary_dim "
            f"{rotary_dim} lies outside the {x.shape[-1]} features")
    return start, x[..., start:start + rotary_dim].astype(jnp.float32)


def _with_rest(x, out, start: int):
    """`out` back between the features of x that pass through."""
    end = start + out.shape[-1]
    if end - start == x.shape[-1]:
        return out
    return jnp.concatenate(
        ([x[..., :start]] if start else []) + [out]
        + ([x[..., end:]] if end < x.shape[-1] else []), axis=-1)


def rotary_interleaved(x, theta: float, rotary_dim: int, start=None):
    """Rotate `rotary_dim` features of x [..., S, D], from `start` (None:
    the LAST ones), by position (axis -2, positions 0..S-1): the pairs
    (2i, 2i+1) turn by pos * theta^(-2i / rotary_dim) (Su et al. 2021, the
    interleaved layout). The rest of D passes through."""
    s = x.shape[-2]
    half = rotary_dim // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0
                            / rotary_dim))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)                    # [S, half]
    start, rot = _turned_part(x, rotary_dim, start)
    pairs = rot.reshape(rot.shape[:-1] + (half, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return _with_rest(x, out.reshape(rot.shape).astype(x.dtype), start)


def rotary_frequencies(theta: float, rotary_dim: int, rope_type="default",
                       factor=1.0, original_max_position=0, beta_fast=32.0,
                       beta_slow=1.0):
    """f_j, j < rotary_dim / 2: position p turns pair j by p * f_j. A static
    float64 table (numpy), made from attrs at trace time.

    "default": f_j = theta^(-2j / rotary_dim) (Su et al. 2021).
    "yarn" (Peng et al. 2023, NTK-by-parts): pairs that turn more than
    `beta_fast` times over `original_max_position` positions keep f_j, pairs
    that turn less than `beta_slow` times get f_j / factor, a linear ramp in
    j between: with c(r) = rotary_dim ln(original / (2 pi r)) / (2 ln theta),
    low = floor(c(beta_fast)), high = ceil(c(beta_slow)), both clamped to
    0..rotary_dim - 1, ramp_j = clip((j - low) / (high - low), 0, 1),
    f_j = (1 - ramp_j) theta^(-2j/rotary_dim)
          + ramp_j theta^(-2j/rotary_dim) / factor."""
    j = np.arange(rotary_dim // 2, dtype=np.float64)
    freq = theta ** (-2.0 * j / rotary_dim)
    if rope_type == "default":
        return freq
    if rope_type != "yarn":
        raise ValueError(f"rotary_embedding: unknown rope_type {rope_type!r}")

    def turns_at(r):
        return rotary_dim * math.log(original_max_position
                                     / (2 * math.pi * r)) / (
            2 * math.log(theta))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), rotary_dim - 1)
    ramp = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 - ramp) * freq + ramp * freq / factor


def stream_angles(positions, freq, sections, ndim: int):
    """Angles [B, (1,) S, half] of x [B, (heads,) S, D] under several
    position streams (Qwen2-VL's multimodal rotary rule): `positions`
    [streams, B, S]; the pairs in order fall into `sections` (their sizes,
    summing to half), and pair j turns by the position of ITS section's
    stream times freq[j]."""
    if sum(sections) != len(freq) or len(sections) != positions.shape[0]:
        raise ValueError(
            f"rotary_embedding: sections {list(sections)} must name one "
            f"size a stream of Positions {tuple(positions.shape)} and sum "
            f"to the {len(freq)} pairs")
    stream = np.repeat(np.arange(len(sections)), sections)       # [half]
    pos = jnp.moveaxis(positions.astype(jnp.float32), 0, -1)[..., stream]
    ang = pos * jnp.asarray(freq, jnp.float32)                # [B, S, half]
    return ang if ndim == 3 else ang[:, None]


def rotary_half(x, freq, rotary_dim: int, scale: float, angles=None,
                start=None):
    """Rotate `rotary_dim` features of x [..., S, D], from `start` (None:
    the LAST ones), over the half-split pairs (j, j + rotary_dim / 2) of
    the turned part by pos * freq[j], cos and sin times `scale`; `angles`
    (`stream_angles`) in place of the row's own positions times freq."""
    s = x.shape[-2]
    half = rotary_dim // 2
    ang = angles if angles is not None else jnp.arange(
        s, dtype=jnp.float32)[:, None] * jnp.asarray(freq, jnp.float32)[None]
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale    # [S, half]
    start, rot = _turned_part(x, rotary_dim, start)
    a, b = rot[..., :half], rot[..., half:]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                          axis=-1).astype(x.dtype)
    return _with_rest(x, out, start)


@register("rotary_embedding", nondiff_slots=("Positions",))
def _rotary_embedding(ctx, ins, attrs):
    """`Positions` [streams, B, S] (optional, layout "half"): several
    position streams, the pairs shared out by the attr `sections`; without
    it the streams are the row's own positions, the sections change no
    number and the op is what it was. The attr `rotary_start` says where
    the `rotary_dim` turned features begin (a partial rotary factor that
    turns the FIRST features of a head); without it they are the last."""
    x = ins["X"][0]
    theta = float(attrs.get("theta", 10000.0))
    rotary_dim = int(attrs.get("rotary_dim", x.shape[-1]))
    start = attrs.get("rotary_start")
    layout = attrs.get("layout", "interleaved")
    rope_type = attrs.get("rope_type", "default")
    scale = float(attrs.get("scale", 1.0))
    if layout == "interleaved":
        if rope_type != "default" or scale != 1.0:
            raise ValueError(
                f"rotary_embedding: rope_type {rope_type!r} or a scale "
                "needs layout \"half\"")
        return {"Out": [rotary_interleaved(x, theta, rotary_dim, start)]}
    if layout != "half":
        raise ValueError(f"rotary_embedding: unknown layout {layout!r}")
    freq = rotary_frequencies(
        theta, rotary_dim, rope_type, float(attrs.get("factor", 1.0)),
        int(attrs.get("original_max_position", 0)),
        float(attrs.get("beta_fast", 32.0)),
        float(attrs.get("beta_slow", 1.0)))
    angles = stream_angles(
        ins["Positions"][0], freq, attrs["sections"],
        x.ndim) if ins.get("Positions") else None
    return {"Out": [rotary_half(x, freq, rotary_dim, scale, angles, start)]}


@register("swiglu")
def _swiglu(ctx, ins, attrs):
    """silu(gate) * up (Shazeer 2020), the product in float32."""
    g, u = ins["Gate"][0], ins["Up"][0]
    out = jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
    return {"Out": [out.astype(g.dtype)]}


@register("relu2")
def _relu2(ctx, ins, attrs):
    """relu(x)^2 (So et al. 2021, Primer), the square in float32."""
    x = ins["X"][0]
    return {"Out": [jnp.square(jax.nn.relu(x.astype(jnp.float32))).astype(
        x.dtype)]}


@register("l2_norm")
def _l2_norm(ctx, ins, attrs):
    """x / sqrt(sum(x^2, last axis) + epsilon) * scale, in float32: a
    head's queries and keys under the delta rule."""
    x = ins["X"][0].astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                          + attrs.get("epsilon", 1e-6))
    return {"Out": [(y * attrs.get("scale", 1.0)).astype(ins["X"][0].dtype)]}


@register("head_gate")
def _head_gate(ctx, ins, attrs):
    """X [..., H, D] times sigmoid(Gate [..., H]): one scalar a head; or,
    with a Gate of X's own shape, one an element. Float32 inside."""
    x, gate = ins["X"][0], ins["Gate"][0]
    factor = jax.nn.sigmoid(gate.astype(jnp.float32))
    if gate.ndim < x.ndim:
        factor = factor[..., None]
    out = x.astype(jnp.float32) * factor
    return {"Out": [out.astype(x.dtype)]}
