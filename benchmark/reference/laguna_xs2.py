"""Plain reference of `laguna_xs2_33b_a3b_ep8`: one expert-parallel rank's
share of the model that poolside/Laguna-XS.2's `config.json` describes
(`model_type` laguna), in straightforward float32 `jax.numpy`: forward,
next-token loss, gradients and Adam.

No kernels, no AMP, no sort and no grouped matmul: attention builds its
`[S, S]` mask and scores a few heads at a time (`reference/mellum2.py`'s
`_attend`, called unedited: causal, and in a sliding layer -inf where i - j
>= `sliding_window`), K and V are repeated to the query heads by
`jnp.repeat`, the routed experts are a loop over the held experts, each run
on every token and weighted by a mask. Matmuls run at precision `highest`.
Nothing is imported from the program; the leaves every such reference has
(`rms_norm`, a SwiGLU, yarn's frequency table, the seeded draws, Adam) are
`reference/mellum2.py`'s, where the equation is the same. The rotary turn
is written out here: it is this model's own. The layer equations (x
`[S, d]`, d = `hidden_size` 2048; heads of 128; no bias anywhere, no
dropout, untied head; eps `rms_norm_eps`):

Published layer n (the layers run here are `first_layer` .. + `layers`):
x <- x + Attn_n(RMSNorm(x)), x <- x + FFN_n(RMSNorm(x)); after the last
layer run one RMSNorm, then the head over the `vocab` rows held.

* Attn_n, with kind = `layer_types[n]` and H =
  `num_attention_heads_per_layer[n]` (48 where full, 64 where sliding), on
  `num_key_value_heads` = 8 KV heads: u the normed input; q = u W_q -> H
  heads of 128, k = u W_k, v = u W_v -> 8 heads each; no per-head norm.
* rotary positions on q and k by `rope_parameters[kind]`: the FIRST t =
  `partial_rotary_factor` x 128 features of a head turn, the other 128 - t
  pass unchanged. Inside the turned part the pairs are (j, j + t/2), j <
  t/2: position p turns pair j by p f_j, (a, b) -> (a cos - b sin, b cos +
  a sin), cos and sin times `attention_factor` (1 where the kind has
  none). "default": f_j = theta^(-2j / t). "yarn" (factor F, original
  length L, beta_fast, beta_slow): c(r) = t ln(L / (2 pi r)) / (2 ln
  theta), low = floor(c(beta_fast)), high = ceil(c(beta_slow)), clamped to
  0..t - 1, ramp_j = clip((j - low) / (high - low), 0, 1), f_j = (1 -
  ramp_j) theta^(-2j / t) + ramp_j theta^(-2j / t) / F: the table is over
  the t turned features, not over the head. A full layer: t = 64, yarn,
  theta 5e5; a sliding layer: t = 128, default, theta 1e4.
* query head h attends KV head h // (H / 8) (groups of 6 where full, of 8
  where sliding): o_h = softmax(q_h k^T / sqrt(128) + M) v, M causal and, in
  a sliding layer, -inf where i - j >= `sliding_window` = 512.
* out = (concat_h(o_h) x sigmoid(u W_g)) W_o, W_g [d, H x 128], an element
  (`gating`).
* FFN_n where `mlp_layer_types[n]` is "dense" (layer 0 alone): a SwiGLU
  W_down(silu(W_gate f) * W_up f) of width `intermediate_size` 8192.
* FFN_n where it is "sparse": s = sigmoid(f W_r) over ALL `experts_total` =
  256 experts, in float32; the `num_experts_per_tok` = 8 largest of s + b,
  b = 0 (a selection bias that no gradient reaches and no step moves: it is
  no leaf here; the program holds it as a buffer of zeros), ties to the
  lower index, no groups; weights s there divided by their sum times
  `moe_routed_scaling_factor` 2.5, on the expert's OUTPUT
  (`moe_apply_router_weight_on_input` false); routed = sum_k w_k
  E_{i_k}(f), each E_i a SwiGLU of width `moe_intermediate_size` 512; plus
  one shared SwiGLU expert of `shared_expert_intermediate_size` 512 on
  every token, unscaled and ungated.

This rank holds experts `expert_offset` .. + `num_experts` of
`experts_total` and adds their terms only; with `num_experts` =
`experts_total` the same code is the uncut layer.

Departures from the published model, each also in the configuration file's
`assumed`: the form of the attention's gate and the router's scoring,
normalisation and bias (config.json names the switch `gating` and the
factor 2.5, not their forms); no per-head q / k norm, no auxiliary loss, no
multi-token head (no key of any); one final norm; the loss is the mean over
the labelled positions' cross entropy, every position but a row's last
carrying the next token (label -100 = none).

`quant` names the control: "fp8" rounds both operands of every matmul the
configuration runs in bf16 to float8_e4m3, the step below; the router,
which the configuration states in float32, stays as it is.
`cfg["assumed"]["fault"]` names one thing done wrong, for the controls
`correct` must fail: "gate_left_out" (out = concat_h(o_h) W_o),
"full_rotary_all" (the full layers turn all 128 features, yarn's table over
128), "rotary_last_half" (the turned half is the LAST 64 of a head),
"full_grouped_by_8" (in a full layer query head h on KV head h // 8, which
leaves two KV heads unread), "window_ignored" (a sliding layer sees the
whole triangle), "scaling_1" (the routed weights times 1, not 2.5).

Memory on a 16 GB chip: float32 weights, gradients and Adam's two moments of
the 766.5 M parameters are 12.26 GB, which leaves 3.4 GB: less than a
gradient program's temporaries at 8,192 tokens (a layer recomputed for its
backward holds q, the gate and the context at `[8192, 8192]` float32, 268 MB
each, K and V repeated to 64 heads, two heads' `[2, 8192, 8192]` scores and
probabilities with their cotangents, about 2.1 GB, and the dense layer's
three `[8192, 8192]`). So `follow` keeps the moments ON THE HOST between
steps and updates LEAF BY LEAF, as `reference/solar_open2.py` does: while a
gradient is taken the chip holds weights and gradients (6.13 GB), and
during the update one leaf's two moments more (the largest leaf, a layer's
experts of one kind, is 134 MB).
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from .mellum2 import (ADAM, HEADS_PER_BLOCK, IGNORE,  # noqa: F401
                      _adam, _attend, _cfg_key, _mm, _normal, _q, rms_norm,
                      rope_frequencies, swiglu_ffn)

__all__ = ["ADAM", "IGNORE", "follow", "init_leaf", "init_params",
           "param_shapes", "buffer_shapes", "vector_leaves"]

SLIDING, FULL = "sliding_attention", "full_attention"


def _fault(cfg):
    return cfg["assumed"].get("fault")


def layers_here(cfg: dict) -> range:
    return range(cfg["first_layer"], cfg["first_layer"] + cfg["layers"])


def is_dense(cfg: dict, n: int) -> bool:
    return cfg["mlp_layer_types"][n] == "dense"


def param_shapes(cfg: dict) -> dict:
    """Every leaf Adam trains."""
    d, hd, nkv = (cfg["hidden_size"], cfg["head_dim"],
                  cfg["num_key_value_heads"])
    held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    fs, fd = cfg["shared_expert_intermediate_size"], cfg["intermediate_size"]
    s = {"embed_tokens": (cfg["vocab"], d), "final_norm_scale": (d,),
         "lm_head_w": (d, cfg["vocab"])}
    for n in layers_here(cfg):
        p, nh = f"l{n}_", cfg["num_attention_heads_per_layer"][n]
        s.update({p + "attn_norm_scale": (d,), p + "ffn_norm_scale": (d,),
                  p + "q_proj_w": (d, nh * hd), p + "k_proj_w": (d, nkv * hd),
                  p + "v_proj_w": (d, nkv * hd), p + "g_proj_w": (d, nh * hd),
                  p + "o_proj_w": (nh * hd, d)})
        if is_dense(cfg, n):
            s.update({p + "mlp_gate_w": (d, fd), p + "mlp_up_w": (d, fd),
                      p + "mlp_down_w": (fd, d)})
            continue
        s.update({p + "router_w": (d, cfg["experts_total"]),
                  p + "experts_gate_w": (held, d, f),
                  p + "experts_up_w": (held, d, f),
                  p + "experts_down_w": (held, f, d),
                  p + "shared_gate_w": (d, fs), p + "shared_up_w": (d, fs),
                  p + "shared_down_w": (fs, d)})
    return s


def buffer_shapes(cfg: dict) -> dict:
    """No state of the reference that no gradient reaches: the selection
    bias is 0 and moves in no step, so it is in no equation here."""
    return {}


def vector_leaves(cfg: dict) -> list:
    """The leaves whose first moment is compared as a VECTOR and not by its
    norm: every layer's `k_proj_w` (`[d, 8 x 128]`, 8 MB). A rotary turn is
    orthogonal, so a leaf's gradient keeps its size wherever in a head the
    turned features lie and whichever table turns them; its direction does
    not. k's projection sees the turn as q's does, at an eighth of the
    size."""
    return [f"l{n}_k_proj_w" for n in layers_here(cfg)]


def init_leaf(cfg: dict, key, name: str):
    """One seeded leaf: matrices normal(0, initializer std), norm scales 1.
    The draw depends on the leaf's place among the sorted names, not on the
    other leaves."""
    shapes = param_shapes(cfg)
    if name.endswith("_scale"):
        return jnp.ones(shapes[name], jnp.float32)
    return _normal(key, sorted(shapes).index(name), shapes[name],
                   cfg["assumed"]["initializer_std"])


def init_params(cfg: dict, key) -> dict:
    return {name: init_leaf(cfg, key, name)
            for name in sorted(param_shapes(cfg))}


# ---------------------------------------------------------------------------
# the mixer
# ---------------------------------------------------------------------------

def rope(x, rope_cfg: dict, fault=None):
    """x [..., S, D]: the first t = `partial_rotary_factor` x D features
    turn over the pairs (j, j + t/2) by pos * f_j, f over t features, cos
    and sin times `attention_factor`; the other D - t pass."""
    s, d = x.shape[-2], x.shape[-1]
    t = int(d * rope_cfg.get("partial_rotary_factor", 1))
    if fault == "full_rotary_all":
        t = d
    freq = jnp.asarray(rope_frequencies(rope_cfg, t), jnp.float32)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    scale = rope_cfg.get("attention_factor", 1.0)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    if fault == "rotary_last_half":
        rest, turn = x[..., :d - t], x[..., d - t:]
    else:
        turn, rest = x[..., :t], x[..., t:]
    a, b = turn[..., :t // 2], turn[..., t // 2:]
    turned = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    parts = [rest, turned] if fault == "rotary_last_half" else [turned, rest]
    return jnp.concatenate(parts, axis=-1)


def attention(u, p, pre, cfg, n, quant=None):
    """Published layer n's mixer on its normed input u [B, S, d]."""
    kind = cfg["layer_types"][n]
    nh, nkv, hd = (cfg["num_attention_heads_per_layer"][n],
                   cfg["num_key_value_heads"], cfg["head_dim"])
    b, s, _ = u.shape
    fault = _fault(cfg)

    def heads(w, count):
        return _mm(u, p[pre + w], quant).reshape(b, s, count, hd).transpose(
            0, 2, 1, 3)                                      # [B, n, S, hd]

    rope_cfg = cfg["rope_parameters"][kind]
    q, k, v = heads("q_proj_w", nh), heads("k_proj_w", nkv), heads(
        "v_proj_w", nkv)
    q, k = rope(q, rope_cfg, fault), rope(k, rope_cfg, fault)
    if fault == "full_grouped_by_8" and kind == FULL:
        # the fault: head h on KV head h // 8, the sliding layers' group
        k, v = (jnp.repeat(t, 8, axis=1)[:, :nh] for t in (k, v))
    else:
        # query head h attends KV head h // (nh / nkv)
        k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    window = cfg["sliding_window"] if (
        kind == SLIDING and fault != "window_ignored") else None
    block = jax.checkpoint(functools.partial(
        _attend, scale=1.0 / math.sqrt(hd), window=window, quant=quant))
    step = min(HEADS_PER_BLOCK, nh)

    def groups(t):                       # [B, nh, S, d] -> [nh/step, B, ..]
        return jnp.moveaxis(t.reshape(b, nh // step, step, s, -1), 1, 0)

    ctx = jax.lax.map(lambda qkv: block(*qkv), (groups(q), groups(k),
                                                groups(v)))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, nh, s, hd)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, nh * hd)
    if cfg["gating"] and fault != "gate_left_out":
        ctx = ctx * jax.nn.sigmoid(_mm(u, p[pre + "g_proj_w"], quant))
    return _mm(ctx, p[pre + "o_proj_w"], quant)


# ---------------------------------------------------------------------------
# the feed-forward part
# ---------------------------------------------------------------------------

def route(xt, router_w, cfg):
    """(idx [T, k], weights [T, k]) over all `experts_total` experts."""
    scores = jax.nn.sigmoid(_mm(xt, router_w))
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(scores),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=1)
    w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    scaling = (1.0 if _fault(cfg) == "scaling_1"
               else cfg["moe_routed_scaling_factor"])
    return idx, w * scaling


def routed_experts(xt, p, pre, cfg, quant=None):
    """The held experts' part of sum_k w_k E_{i_k}(x): (out [T, d],
    idx [T, k]). A loop (`lax.scan`) over the held experts, each on every
    token, weighted by a mask."""
    idx, w = route(xt, p[pre + "router_w"], cfg)
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


def expert_layer(f, p, pre, cfg, quant=None):
    """(routed part of the held experts + the shared expert, idx)."""
    ft = f.reshape(-1, f.shape[-1])
    routed, idx = routed_experts(ft, p, pre, cfg, quant)
    y = routed + swiglu_ffn(ft, p[pre + "shared_gate_w"],
                            p[pre + "shared_up_w"], p[pre + "shared_down_w"],
                            quant)
    return y.reshape(f.shape), idx


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def layer(x, p, n, cfg, quant=None):
    """Published layer n: (x_out, idx of the routed choice; of a dense
    layer an empty choice)."""
    pre, eps = f"l{n}_", cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, p[pre + "attn_norm_scale"], eps), p, pre,
                      cfg, n, quant)
    f = rms_norm(x, p[pre + "ffn_norm_scale"], eps)
    if is_dense(cfg, n):
        y = swiglu_ffn(f, p[pre + "mlp_gate_w"], p[pre + "mlp_up_w"],
                       p[pre + "mlp_down_w"], quant)
        return x + y, jnp.zeros((0, cfg["num_experts_per_tok"]), jnp.int32)
    y, idx = expert_layer(f, p, pre, cfg, quant)
    return x + y, idx


def forward(p, ids, cfg, quant=None):
    """(hidden states after the final norm [B, S, d], the routed choice of
    the first SPARSE layer [B*S, k])."""
    x = p["embed_tokens"][ids]
    first_idx = None
    for n in layers_here(cfg):
        # a layer keeps its input alone for the backward and is recomputed
        x, idx = jax.checkpoint(
            functools.partial(layer, n=n, cfg=cfg, quant=quant))(x, p)
        if first_idx is None and not is_dense(cfg, n):
            first_idx = idx
    return rms_norm(x, p["final_norm_scale"], cfg["rms_norm_eps"]), first_idx


def loss_sum(params, ids, labels, cfg, quant=None):
    """(sum of the labelled positions' cross entropies over the rows given,
    the first sparse layer's routed choice)."""
    x, idx = forward(params, ids, cfg, quant)
    logits = _mm(x, params["lm_head_w"], quant)
    keep = labels != IGNORE
    safe = jnp.where(keep, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, safe[..., None], -1)[..., 0]
    return jnp.sum(jnp.where(keep, lse - picked, 0.0)), idx


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _block_grad(params, ids, labels, cfg_key, quant):
    cfg = json.loads(cfg_key)
    (val, idx), grads = jax.value_and_grad(loss_sum, has_aux=True)(
        params, ids, labels, cfg, quant)
    return val, idx, grads


def follow(cfg: dict, fresh_params, batches: list, rows_per_block: int,
           quant=None) -> dict:
    """Train `len(batches)` Adam steps from `fresh_params()` (a call that
    makes the seeded weights anew, so that no second copy of them has to
    live through the steps), each batch a dict of host arrays `ids` [B, S]
    and `labels` [B, S], the gradient taken in blocks of `rows_per_block`
    rows. Adam's moments wait on the host between steps and every leaf is
    updated by itself (the module's docstring says why). Returns each
    step's loss, the routed choice of the first sparse layer at step 1
    ([B*S, k]), the per-leaf norms of Adam's first moment and of the
    parameters' change after the last step, and the first moment itself of
    `vector_leaves`."""
    key = _cfg_key(cfg)
    params = fresh_params()
    vectors_of = set(vector_leaves(cfg))
    moments = {}                      # leaf -> (m, v) as host arrays
    losses, first_idx, moment1, vectors = [], None, {}, {}
    for t, batch in enumerate(batches, start=1):
        n_rows = batch["ids"].shape[0]
        labelled = int((batch["labels"] != IGNORE).sum())
        total, grads, idxs = 0.0, None, []
        for lo in range(0, n_rows, rows_per_block):
            sl = slice(lo, lo + rows_per_block)
            val, idx, g = _block_grad(params, batch["ids"][sl],
                                      batch["labels"][sl], key, quant)
            total = total + float(val)
            idxs.append(idx)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        if first_idx is None:
            first_idx = jnp.concatenate(idxs, axis=0)
        losses.append(total / labelled)
        last = t == len(batches)
        for name in sorted(params):
            g = grads.pop(name) / labelled
            m, v = moments.pop(name, (None, None))
            m = jnp.zeros_like(g) if m is None else jax.device_put(m)
            v = jnp.zeros_like(g) if v is None else jax.device_put(v)
            # `_adam` over a tree of one leaf
            params[name], m, v = _adam(params[name], m, v, g, float(t))
            if last:
                moment1[name] = float(jnp.linalg.norm(m))
                if name in vectors_of:
                    vectors[name] = np.asarray(m, np.float32)
            else:
                moments[name] = (np.asarray(m), np.asarray(v))
            del g, m, v
    del grads, moments
    # the seeded weights once more (6.13 GB with the trained ones: nothing
    # else is on the chip by now)
    p0 = fresh_params()
    delta = {k: float(jnp.linalg.norm(params[k] - p0[k])) for k in params}
    return {"losses": losses, "moment1_norms": moment1, "delta_norms": delta,
            "moment1_vectors": vectors, "first_route": first_idx}
