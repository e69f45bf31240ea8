"""Plain reference of `lfm2_24b_a2b_ep8`: one expert-parallel rank's share of
LiquidAI/LFM2-24B-A2B (`model_type` lfm2_moe) in straightforward float32
`jax.numpy`: forward, next-token loss, gradients and Adam.

No kernels, no AMP, no sort and no grouped matmul: the convolution is its
shifted products written out, attention a plain masked softmax a block of
`reference_tokens_per_block` queries at a time (so that a row of 8,192
fits), the routed experts a loop over the held experts, each run on every
token and weighted by a mask. Matmuls run at precision `highest`. Nothing
is imported from the program; the leaves that every such reference has
(`rms_norm`, the half-split rotary turn, a SwiGLU, the seeded draw, Adam)
are `reference/mellum2.py`'s, called with this share. The equations (d = `hidden_size` 2048, S a row's length,
eps = `norm_eps` 1e-5, no bias anywhere, no dropout; the layers run here
are published layers `first_layer` .. + `layers` of 40):

* layer n: x <- x + Mixer_n(RMSNorm(x)), then x <- x + FF_n(RMSNorm(x)) (the
  published `operator_norm` and `ffn_norm`); after the last layer one
  RMSNorm (the published code calls it `embedding_norm`; it is applied to
  the OUTPUT of the last layer, not to the embeddings); logits =
  RMSNorm(x) E^T with E the token embedding: the head is TIED.
* short-convolution mixer (`layer_types[n]` "conv"): [B_t | C_t | u_t] =
  x_t W_in (`W_in` [d, 3d], thirds in that order); g_t = B_t * u_t; c_t =
  sum_{j=0..K-1} w_j * g_{t-(K-1)+j} with w [K, d] a tap a channel, K =
  `conv_L_cache` = 3, positions before the row's start read as zeros, no
  bias (`conv_bias` false) and no activation; y_t = C_t * c_t; out = y_t
  W_out ([d, d]). The gates are plain products: no sigmoid, no silu.
* attention (`layer_types[n]` "full_attention"): q = x W_q (32 heads of
  64), k = x W_k, v = x W_v (8 KV heads of 64); q <- RMSNorm_64(q), k <-
  RMSNorm_64(k) a head, each with one learned scale of 64 shared by the
  heads; THEN rotary positions on all 64 features, half-split pairs (j,
  j + 32), theta 1e6, the default rule; query head h on KV head h // 4,
  causal over the whole row, scale 64^-0.5; W_o [d, d].
* feed-forward part: layers n < `num_dense_layers` W_2(silu(W_1 x) * W_3 x)
  at `intermediate_size` 11776; every other layer 64 experts of that form at
  `moe_intermediate_size` 1536. Router: s = sigmoid(x W_r) over ALL
  `experts_total` in float32; the `num_experts_per_tok` experts with the
  largest s + b (b [64] float32, a buffer no gradient reaches: the
  published `expert_bias`), ties to the lower index; their weights are s
  itself (without b), divided by (their sum + 1e-6) (`norm_topk_prob`),
  times `routed_scaling_factor`; no shared expert, no capacity, no drops.
  This rank holds experts `expert_offset` .. + `num_experts` and adds their
  terms only; with `num_experts` = `experts_total` the same code is the
  uncut layer.
* objective: mean next-token cross entropy over the `vocab` rows held,
  every position but a row's last (label -100 = none).

Departures from the published model, each in the configuration's `assumed`:
the tied head (config.json has no key; the family's convention); `head_dim`
64 = 2048 / 32 (not given); b starts at 0 and no step moves it (the
family's balance update runs outside the gradient and is not built); no
auxiliary balance loss and no multi-token head (no key of either).

`quant` names the control: "fp8" rounds both operands of every matmul the
configuration runs in bf16 to float8_e4m3, the step below; router, norms,
the gates and the convolution stay as they are. `cfg["assumed"]["fault"]`
names one thing done wrong, for the controls that `correct` must fail:
"gate_left_out" (y = c, the second gate dropped), "taps_reversed" (w_2,
w_1, w_0), "qk_norm_left_out", "head_untied" (a head of its own, drawn
like any matrix).
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from .mellum2 import (ADAM, IGNORE, _adam, _cfg_key, _mm,  # noqa: F401
                      _normal, rms_norm, rope, swiglu_ffn)

__all__ = ["ADAM", "IGNORE", "follow", "init_leaf", "init_params",
           "param_shapes", "buffer_shapes", "split_state", "vector_leaves"]

CONV = "conv"
NORM_TOPK_EPS = 1e-6


def _fault(cfg):
    return cfg["assumed"].get("fault")


def layers_here(cfg: dict) -> range:
    return range(cfg["first_layer"], cfg["first_layer"] + cfg["layers"])


def is_moe_layer(cfg: dict, n: int) -> bool:
    return n >= cfg["num_dense_layers"]


def _sound_shapes(cfg: dict) -> dict:
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    s = {"embed_tokens": (cfg["vocab"], h), "final_norm_scale": (h,)}
    for n in layers_here(cfg):
        p = f"l{n}_"
        s.update({p + "operator_norm_scale": (h,),
                  p + "ffn_norm_scale": (h,)})
        if cfg["layer_types"][n] == CONV:
            s.update({p + "conv_in_proj_w": (h, 3 * h),
                      p + "conv_w": (cfg["conv_L_cache"], h),
                      p + "conv_out_proj_w": (h, h)})
        else:
            s.update({p + "q_proj_w": (h, nh * hd),
                      p + "k_proj_w": (h, nkv * hd),
                      p + "v_proj_w": (h, nkv * hd),
                      p + "q_norm_scale": (hd,), p + "k_norm_scale": (hd,),
                      p + "o_proj_w": (nh * hd, h)})
        if is_moe_layer(cfg, n):
            s.update({p + "router_w": (h, cfg["experts_total"]),
                      p + "experts_gate_w": (held, h, f),
                      p + "experts_up_w": (held, h, f),
                      p + "experts_down_w": (held, f, h)})
        else:
            i = cfg["intermediate_size"]
            s.update({p + "mlp_gate_w": (h, i), p + "mlp_up_w": (h, i),
                      p + "mlp_down_w": (i, h)})
    return s


def param_shapes(cfg: dict) -> dict:
    """Every leaf Adam trains (under the fault "head_untied" one more, the
    head)."""
    s = _sound_shapes(cfg)
    if _fault(cfg) == "head_untied":
        s["lm_head_w"] = (cfg["hidden_size"], cfg["vocab"])
    return s


def buffer_shapes(cfg: dict) -> dict:
    """The selection bias of every expert layer: state no gradient
    reaches and no optimizer updates."""
    return {f"l{n}_router_bias": (cfg["experts_total"],)
            for n in layers_here(cfg) if is_moe_layer(cfg, n)}


def init_leaf(cfg: dict, key, name: str):
    """One seeded leaf: matrices and taps normal(0, initializer std), norm
    scales 1, selection biases 0 (all under `assumed`). The draw depends on
    the leaf's place among the sound model's sorted names, not on the other
    leaves, so a fault's extra leaf moves none of them."""
    shapes = param_shapes(cfg)
    if name.endswith("_scale"):
        return jnp.ones(shapes[name], jnp.float32)
    if name.endswith("router_bias"):
        return jnp.zeros(buffer_shapes(cfg)[name], jnp.float32)
    sound = sorted(_sound_shapes(cfg))
    idx = sound.index(name) if name in sound else len(sound)
    return _normal(key, idx, shapes[name], cfg["assumed"]["initializer_std"])


def init_params(cfg: dict, key) -> dict:
    """Every trained leaf and every buffer, seeded."""
    return {name: init_leaf(cfg, key, name)
            for name in sorted(dict(param_shapes(cfg), **buffer_shapes(cfg)))}


def split_state(cfg: dict, params: dict) -> tuple:
    """(trained leaves, buffers) of what `init_params` made."""
    buffers = {n: params[n] for n in buffer_shapes(cfg)}
    return {n: a for n, a in params.items() if n not in buffers}, buffers


def short_conv(x, p, pre, cfg, quant=None):
    """W_out (C * conv(B * u)), the convolution its K shifted products."""
    h, s = cfg["hidden_size"], x.shape[1]
    bcx = _mm(x, p[pre + "conv_in_proj_w"], quant)
    b, c, u = bcx[..., :h], bcx[..., h:2 * h], bcx[..., 2 * h:]
    g = b * u
    w = p[pre + "conv_w"]
    if _fault(cfg) == "taps_reversed":
        w = w[::-1]
    k = w.shape[0]
    # g_{t-d}: the row moved d positions on, zeros where it began
    conv = sum(w[j] * jnp.pad(g, ((0, 0), (k - 1 - j, 0), (0, 0)))[:, :s]
               for j in range(k))
    y = conv if _fault(cfg) == "gate_left_out" else c * conv
    return _mm(y, p[pre + "conv_out_proj_w"], quant)


def _attend_block(q, start, k, v, scale, quant):
    """Causal softmax attention of one block of queries, q [B, nh, Q, d]
    from position `start`, over all the keys k, v [B, nh, S, d]."""
    scores = _mm(q, jnp.swapaxes(k, -1, -2), quant) * scale
    i = start + jnp.arange(q.shape[2])[:, None]
    j = jnp.arange(k.shape[2])[None, :]
    scores = jnp.where(i >= j, scores, -jnp.inf)
    return _mm(jax.nn.softmax(scores, axis=-1), v, quant)


def attention(x, p, pre, cfg, quant=None):
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    b, s, _ = x.shape

    def heads(w, n):
        return _mm(x, p[pre + w], quant).reshape(b, s, n, hd).transpose(
            0, 2, 1, 3)                                      # [B, n, S, hd]

    q, k, v = heads("q_proj_w", nh), heads("k_proj_w", nkv), heads(
        "v_proj_w", nkv)
    if _fault(cfg) != "qk_norm_left_out":
        q = rms_norm(q, p[pre + "q_norm_scale"], cfg["norm_eps"])
        k = rms_norm(k, p[pre + "k_norm_scale"], cfg["norm_eps"])
    q, k = rope(q, cfg["rope_parameters"]), rope(k, cfg["rope_parameters"])
    # query head h attends KV head h // (nh / nkv)
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    step = cfg["reference_tokens_per_block"]
    step = step if s % step == 0 else s
    block = jax.checkpoint(functools.partial(
        _attend_block, scale=1.0 / math.sqrt(hd), quant=quant))
    blocks = jnp.moveaxis(q.reshape(b, nh, s // step, step, hd), 2, 0)
    ctx = jax.lax.map(lambda qs: block(qs[0], qs[1], k, v),
                      (blocks, jnp.arange(0, s, step)))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(b, nh, s, hd)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, nh * hd)
    return _mm(ctx, p[pre + "o_proj_w"], quant)


def route(xt, router_w, router_bias, cfg):
    """(idx [T, k], weights [T, k]) over all `experts_total` experts."""
    scores = jax.nn.sigmoid(_mm(xt, router_w))
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(scores + router_bias),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + NORM_TOPK_EPS)
    return idx, w * cfg["routed_scaling_factor"]


def routed_experts(xt, p, pre, cfg, quant=None):
    """The held experts' part of sum_k w_k E_{i_k}(x): (out [T, h],
    idx [T, k]). A loop (`lax.scan`) over the held experts, each on every
    token, weighted by a mask."""
    idx, w = route(xt, p[pre + "router_w"], p[pre + "router_bias"], cfg)
    held = cfg.get("expert_offset", 0) + jnp.arange(cfg["num_experts"])
    # w_e[e, t]: the weight token t gives held expert e, 0 if not chosen
    w_e = jnp.sum(jnp.where(idx[None] == held[:, None, None], w[None], 0.0),
                  axis=2)

    def one_expert(out, e):
        gate, up, down, weight = e
        return out + weight[:, None] * swiglu_ffn(xt, gate, up, down,
                                                  quant), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(xt),
        (p[pre + "experts_gate_w"], p[pre + "experts_up_w"],
         p[pre + "experts_down_w"], w_e))
    return out, idx


def layer(x, p, n, cfg, quant=None):
    """Published layer n: (x_out, idx of the routed choice or None)."""
    pre = f"l{n}_"
    eps = cfg["norm_eps"]
    mixer = short_conv if cfg["layer_types"][n] == CONV else attention
    x = x + mixer(rms_norm(x, p[pre + "operator_norm_scale"], eps), p, pre,
                  cfg, quant)
    f = rms_norm(x, p[pre + "ffn_norm_scale"], eps)
    if not is_moe_layer(cfg, n):
        return x + swiglu_ffn(f, p[pre + "mlp_gate_w"], p[pre + "mlp_up_w"],
                              p[pre + "mlp_down_w"], quant), None
    routed, idx = routed_experts(f.reshape(-1, f.shape[-1]), p, pre, cfg,
                                 quant)
    return x + routed.reshape(x.shape), idx


def forward(p, ids, cfg, quant=None):
    """(hidden states after the last norm [B, S, h], the routed choice of
    the first expert layer [B*S, k])."""
    x = p["embed_tokens"][ids]
    first_idx = None
    for n in layers_here(cfg):
        # a layer keeps its input alone for the backward and is recomputed
        x, idx = jax.checkpoint(
            functools.partial(layer, n=n, cfg=cfg, quant=quant))(x, p)
        if first_idx is None and idx is not None:
            first_idx = idx
    return rms_norm(x, p["final_norm_scale"], cfg["norm_eps"]), first_idx


def loss_sum(trained, buffers, ids, labels, cfg, quant=None):
    """(sum of the labelled positions' cross entropies over the rows
    given, the first expert layer's routed choice)."""
    x, idx = forward({**trained, **buffers}, ids, cfg, quant)
    head = (trained["lm_head_w"] if _fault(cfg) == "head_untied"
            else trained["embed_tokens"].T)
    logits = _mm(x, head, quant)
    keep = labels != IGNORE
    safe = jnp.where(keep, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, safe[..., None], -1)[..., 0]
    return jnp.sum(jnp.where(keep, lse - picked, 0.0)), idx


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _block_grad(trained, buffers, ids, labels, cfg_key, quant):
    cfg = json.loads(cfg_key)
    (val, idx), grads = jax.value_and_grad(loss_sum, has_aux=True)(
        trained, buffers, ids, labels, cfg, quant)
    return val, idx, grads


def vector_leaves(cfg: dict) -> list:
    """The leaves whose first moment is compared as a VECTOR and not by its
    norm: every mixer's taps, the per-head norms' scales and the last norm's
    scale. Each is small, each gradient a sum of like terms over every
    position (and head), so its direction is steady from seed to seed; and
    each fault this model admits turns one of them where it hardly moves a
    norm: the taps reversed or the second gate dropped turn the taps'
    gradient, q and k left without their norm leave the scales' at 0, a
    head of its own turns the last norm's."""
    out = ["final_norm_scale"]
    for n in layers_here(cfg):
        out += ([f"l{n}_conv_w"] if cfg["layer_types"][n] == CONV
                else [f"l{n}_q_norm_scale", f"l{n}_k_norm_scale"])
    return out


def follow(cfg: dict, fresh_params, batches: list, rows_per_block: int,
           quant=None) -> dict:
    """Train `len(batches)` Adam steps from `fresh_params()` (a call that
    makes the seeded weights anew, so that no second copy of them has to
    live through the steps), each batch a dict of host arrays `ids` [B, S]
    and `labels` [B, S], the gradient taken in blocks of `rows_per_block`
    rows. Returns each step's loss, the routed choice of the first expert
    layer at step 1 ([B*S, k]), the per-leaf norms of Adam's first moment
    and of the parameters' change after the last step, and the first moment
    itself of `vector_leaves`."""
    key = _cfg_key(cfg)
    params, buffers = split_state(cfg, fresh_params())
    # Adam's moments wait on the host while a gradient is taken: beside the
    # gradient program's temporaries the 5.2 GB of them do not fit a 16 GB
    # chip
    m = v = None
    losses, first_idx = [], None
    for t, batch in enumerate(batches, start=1):
        n_rows = batch["ids"].shape[0]
        labelled = int((batch["labels"] != IGNORE).sum())
        total, grads, idxs = 0.0, None, []
        for lo in range(0, n_rows, rows_per_block):
            sl = slice(lo, lo + rows_per_block)
            val, idx, g = _block_grad(params, buffers, batch["ids"][sl],
                                      batch["labels"][sl], key, quant)
            total = total + float(val)
            idxs.append(idx)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        if first_idx is None:
            first_idx = jnp.concatenate(idxs, axis=0)
        grads = jax.tree.map(lambda g: g / labelled, grads)
        losses.append(total / labelled)
        if m is None:
            m = jax.tree.map(jnp.zeros_like, params)
            v = jax.tree.map(jnp.zeros_like, params)
        params, m, v = _adam(params, jax.device_put(m), jax.device_put(v),
                             grads, float(t))
        del grads
        if t < len(batches):
            m, v = jax.device_get((m, v))
    moment1 = {k: float(jnp.linalg.norm(a)) for k, a in m.items()}
    vectors = {k: np.asarray(m[k], np.float32) for k in vector_leaves(cfg)}
    del m, v
    p0, _ = split_state(cfg, fresh_params())
    delta = {k: float(jnp.linalg.norm(params[k] - p0[k])) for k in params}
    return {"losses": losses, "moment1_norms": moment1, "delta_norms": delta,
            "moment1_vectors": vectors, "first_route": first_idx}
