"""Plain reference of `solar_open2_250b_ep40_tp8`: one chip's share of the
model that upstage/Solar-Open2-250B's `config.json` describes (`model_type`
solar_open2), in straightforward float32 `jax.numpy`: forward, next-token
loss, gradients and Adam.

No kernels, no AMP, no chunks, no triangular solve, no sort and no grouped
matmul: the gated delta rule is the recurrence itself, one token after
another (`reference/ling3.py`'s `delta_rule`, called unedited: a `lax.scan`
in blocks of `reference_scan_tokens_per_block` tokens that keep their
opening state for the backward); attention builds its `[S, S]` mask and
scores; the routed experts are a loop over the held experts, each run on
every token and weighted by a mask. Matmuls run at precision `highest`.
Nothing is imported from the program; the leaves every such reference has
(`rms_norm`, `l2_norm`, the causal convolution, a SwiGLU, the seeded draws)
are `reference/ling3.py`'s. The layer equations (x `[S, d]`, d =
`hidden_size` 4096; heads of 128; no bias but the output gate's, no
dropout, no rotary positions anywhere: `use_rope` false; untied head):

Published layer n (the layers run here are `first_layer` .. + `layers`):
x <- x + Attn_n(RMSNorm(x)), x <- x + FFN_n(RMSNorm(x)), eps
`rms_norm_eps`; after the last layer a final RMSNorm, then the head over
the `vocab` rows held. Attn_n is softmax attention where n is in
`gqa_layers` and KDA elsewhere. FFN_n is the expert layer in every layer
(`first_k_dense_replace` 0; `intermediate_size` is read by no layer).

* KDA (per head h of the `linear_attn_config.num_heads` held; float32
  state S [128, 128], zero at a row's start). q~ = u W_q, k~ = u W_k, v~ =
  u W_v, each [S, H x 128]; each through a causal depthwise conv of
  `short_conv_kernel_size` = 4 taps without bias (position t sees t - 3 ..
  t) and then silu. q_h = l2norm(q~_h) x 128^-0.5, k_h = l2norm(k~_h).
  Decay, a channel: g_t = -exp(A_log_h) x softplus((u_t W_f1) W_f2 +
  dt_bias), W_f1 [d, 128], W_f2 [128, H x 128] (`kda_use_full_proj` false),
  any number <= 0: NO lower bound; alpha_t = exp(g_t). beta_t = 2 x
  sigmoid(u_t W_beta), a scalar a head in (0, 2) (`kda_allow_neg_eigval`).
      S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t
  o_h <- RMSNorm_128(o_h) (one weight of 128 for all heads) x sigmoid((u
  W_g1) W_g2 + b_g)_h, an element; out = concat_h(o_h) W_o.
* attention (n in `gqa_layers`): q = u W_q (`num_attention_heads` held heads
  of 128), k = u W_k, v = u W_v (`num_key_value_heads` held), no rotary, no
  per-head norm; query head h on KV head h // (heads / KV heads); causal
  softmax of q k^T / sqrt(128) times v; out = (concat_h(o_h) x sigmoid(u
  W_gate)) W_o, W_gate [d, H x 128] (`use_gqa_gate`).
* expert layer: s = sigmoid(u W_r) over ALL `experts_total` experts, in
  float32; the `num_experts_per_tok` largest s + b (b a buffer of zeros no
  gradient reaches and no step moves), ties to the lower index; weights s
  there divided by their sum (`norm_topk_prob`) times
  `routed_scaling_factor`; routed = sum_k w_k E_{i_k}(u), each E_i a SwiGLU
  W_down(silu(W_gate u) * W_up u) of width `moe_intermediate_size`; plus
  one shared expert of the same form and width.

This chip holds experts `expert_offset` .. + `n_routed_experts` of
`experts_total` and the heads the three counts give, and adds their terms
only: the projections into heads have the held heads' columns, W_o their
rows. With every count at its total the same code is the uncut layer.

Departures from the published model, each also in the configuration file's
`assumed`: the router's scoring and its bias (config.json names neither),
the form of the attention's gate, the draws of `A_log`, `dt_bias` and the
taps, one final norm, no auxiliary loss and no multi-token head.

`quant` names the control: "fp8" rounds both operands of every product the
configuration runs in bf16 to float8_e4m3, the step below, forward (as
`reference/ling3.py` `_q`). `cfg["assumed"]["fault"]` names one thing done
wrong, for the controls `correct` must fail: "beta_unscaled" (beta =
sigmoid(.), the factor 2 left out), "bounded_gate" (the sibling family's g =
-5 sigmoid(exp(A_log) x (.)) in the unbounded gate's place),
"attn_gate_left_out" (out = concat_h(o_h) W_o). `cfg["assumed"]["dt_range"]`
is no fault: it draws `dt_bias` from another range of steps than the
configuration's own, for the driver's control `steep_decay`, which puts g
where only a form of the scan without a bound on it is right.

Memory: float32 weights, gradients and Adam's two moments of the 840.9 M
parameters are 13.45 GB, which a 16 GB chip does not hold beside a gradient
program's temporaries. `follow` keeps the moments ON THE HOST between steps
and updates LEAF BY LEAF: while a gradient is taken the chip holds weights
and gradients (6.7 GB), and during the update one leaf's two moments more.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from .ling3 import (ADAM, DT_FLOOR, DT_MAX, DT_MIN, IGNORE,  # noqa: F401
                    _adam, _cfg_key, _mm, _normal, _q, _uniform, causal_conv,
                    delta_rule, l2_norm, rms_norm, swiglu_ffn)

__all__ = ["ADAM", "IGNORE", "follow", "init_leaf", "init_params",
           "param_shapes", "buffer_shapes", "split_state", "vector_leaves"]

# the bound of the sibling family's gate, which the fault "bounded_gate"
# puts in this one's place
BOUNDED_GATE = -5.0


def _fault(cfg):
    return cfg["assumed"].get("fault")


def layers_here(cfg: dict) -> range:
    return range(cfg["first_layer"], cfg["first_layer"] + cfg["layers"])


def is_softmax_layer(cfg: dict, n: int) -> bool:
    return n in cfg["gqa_layers"]


def param_shapes(cfg: dict) -> dict:
    """Every leaf Adam trains."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    lin = cfg["linear_attn_config"]
    lh, ld, kc = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    held, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    s = {"embed_tokens": (cfg["vocab"], d), "final_norm_scale": (d,),
         "lm_head_w": (d, cfg["vocab"])}
    for n in layers_here(cfg):
        p = f"l{n}_"
        s.update({p + "attn_norm_scale": (d,), p + "ffn_norm_scale": (d,)})
        if is_softmax_layer(cfg, n):
            s.update({p + "q_proj_w": (d, nh * hd),
                      p + "k_proj_w": (d, nkv * hd),
                      p + "v_proj_w": (d, nkv * hd),
                      p + "g_proj_w": (d, nh * hd),
                      p + "o_proj_w": (nh * hd, d)})
        else:
            for name in "qkv":
                s[p + name + "_proj_w"] = (d, lh * ld)
                s[p + name + "_conv_w"] = (kc, lh * ld)
            s.update({p + "f_a_proj_w": (d, ld),
                      p + "f_b_proj_w": (ld, lh * ld),
                      p + "A_log": (lh,), p + "dt_bias": (lh * ld,),
                      p + "b_proj_w": (d, lh),
                      p + "g_a_proj_w": (d, ld),
                      p + "g_b_proj_w": (ld, lh * ld),
                      p + "g_b_proj_b": (lh * ld,),
                      p + "o_norm_scale": (ld,),
                      p + "o_proj_w": (lh * ld, d)})
        s.update({p + "router_w": (d, cfg["experts_total"]),
                  p + "experts_gate_w": (held, d, f),
                  p + "experts_up_w": (held, d, f),
                  p + "experts_down_w": (held, f, d),
                  p + "shared_gate_w": (d, f), p + "shared_up_w": (d, f),
                  p + "shared_down_w": (f, d)})
    return s


def buffer_shapes(cfg: dict) -> dict:
    """The selection bias of every expert layer: state no gradient
    reaches and no optimizer updates."""
    return {f"l{n}_router_bias": (cfg["experts_total"],)
            for n in layers_here(cfg)}


def vector_leaves(cfg: dict) -> list:
    """The leaves whose first moment is compared as a VECTOR and not by its
    norm, in every KDA layer, as `reference/ling3.py` chooses them and for
    its reasons: the three short convolutions' kernels, the output norm's
    scale and `beta`'s projection (each a sum of like terms over every
    position, its direction steady from seed to seed; `beta`'s turns with
    beta's factor 2 where its norm merely halves)."""
    return [f"l{n}_{leaf}" for n in layers_here(cfg)
            if not is_softmax_layer(cfg, n)
            for leaf in ("q_conv_w", "k_conv_w", "v_conv_w", "o_norm_scale",
                         "b_proj_w")]


def init_leaf(cfg: dict, key, name: str):
    """One seeded leaf, by the rules under `assumed`: matrices normal(0,
    initializer std); norm scales 1; the output gate's bias and the
    selection bias 0; the conv taps uniform(+-1 / sqrt(K)) = +-1/2; A_log =
    log uniform(1, 16); dt_bias the inverse softplus of a log-uniform step
    in [0.001, 0.1] (or in `assumed.dt_range`, the control `steep_decay`)
    floored at 1e-4. The draw depends on the leaf's place among the sorted
    names, not on the other leaves."""
    shapes = dict(param_shapes(cfg), **buffer_shapes(cfg))
    idx, shape = sorted(shapes).index(name), shapes[name]
    if name.endswith("_scale"):
        return jnp.ones(shape, jnp.float32)
    if name.endswith(("router_bias", "g_b_proj_b")):
        return jnp.zeros(shape, jnp.float32)
    if name.endswith("conv_w"):
        bound = 1.0 / math.sqrt(shape[0])
        return _uniform(key, idx, shape, -bound, bound)
    if name.endswith("A_log"):
        return jnp.log(_uniform(key, idx, shape, 1.0, 16.0))
    if name.endswith("dt_bias"):
        lo, hi = cfg["assumed"].get("dt_range", (DT_MIN, DT_MAX))
        dt = jnp.maximum(jnp.exp(_uniform(key, idx, shape, math.log(lo),
                                          math.log(hi))), DT_FLOOR)
        return dt + jnp.log(-jnp.expm1(-dt))      # softplus^-1(dt)
    return _normal(key, idx, shape, cfg["assumed"]["initializer_std"])


def init_params(cfg: dict, key) -> dict:
    """Every trained leaf and every buffer, seeded."""
    return {name: init_leaf(cfg, key, name)
            for name in sorted(dict(param_shapes(cfg), **buffer_shapes(cfg)))}


def split_state(cfg: dict, params: dict) -> tuple:
    """(trained leaves, buffers) of what `init_params` made."""
    buffers = {n: params[n] for n in buffer_shapes(cfg)}
    return {n: a for n, a in params.items() if n not in buffers}, buffers


# ---------------------------------------------------------------------------
# the mixers
# ---------------------------------------------------------------------------

def log_decay(u, p, pre, cfg, quant=None):
    """g [B, S, H, 128]: -exp(A_log) softplus((u W_f1) W_f2 + dt_bias)."""
    lin = cfg["linear_attn_config"]
    b, s, _ = u.shape
    pre_gate = (_mm(_mm(u, p[pre + "f_a_proj_w"], quant),
                    p[pre + "f_b_proj_w"], quant)
                + p[pre + "dt_bias"]).reshape(b, s, lin["num_heads"],
                                              lin["head_dim"])
    rate = jnp.exp(p[pre + "A_log"])[:, None]
    if _fault(cfg) == "bounded_gate":
        return BOUNDED_GATE * jax.nn.sigmoid(rate * pre_gate)
    return -rate * jax.nn.softplus(pre_gate)


def kda_attention(u, p, pre, cfg, quant=None):
    """(the layer's output, the least log decay a channel has in it)."""
    lin = cfg["linear_attn_config"]
    nh, hd = lin["num_heads"], lin["head_dim"]
    b, s, _ = u.shape

    def conv_heads(name):
        x = _mm(u, p[pre + name + "_proj_w"], quant)
        return jax.nn.silu(causal_conv(x, p[pre + name + "_conv_w"])
                           ).reshape(b, s, nh, hd)

    q, k, v = conv_heads("q"), conv_heads("k"), conv_heads("v")
    q, k = l2_norm(q) * hd ** -0.5, l2_norm(k)
    g = log_decay(u, p, pre, cfg, quant)
    beta = jax.nn.sigmoid(_mm(u, p[pre + "b_proj_w"], quant))
    if cfg["kda_allow_neg_eigval"] and _fault(cfg) != "beta_unscaled":
        beta = 2.0 * beta
    o = delta_rule(_q(q, quant), _q(k, quant), _q(v, quant), g, beta, cfg)
    o = rms_norm(o, p[pre + "o_norm_scale"], cfg["rms_norm_eps"])
    gate = (_mm(_mm(u, p[pre + "g_a_proj_w"], quant), p[pre + "g_b_proj_w"],
                quant) + p[pre + "g_b_proj_b"])
    o = o.reshape(b, s, nh * hd) * jax.nn.sigmoid(gate)
    return _mm(o, p[pre + "o_proj_w"], quant), jnp.min(g)


def _attend(q, k, v, scale, quant):
    """Causal softmax attention: q, k, v [B, n, S, d]."""
    s = q.shape[2]
    scores = _mm(q, jnp.swapaxes(k, -1, -2), quant) * scale
    seen = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    return _mm(jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), v,
               quant)


def attention(u, p, pre, cfg, quant=None):
    """(the layer's output, 0.0: no decay here)."""
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    b, s, _ = u.shape

    def heads(w, n):
        return _mm(u, p[pre + w], quant).reshape(b, s, n, hd).transpose(
            0, 2, 1, 3)                                      # [B, n, S, hd]

    q, k, v = heads("q_proj_w", nh), heads("k_proj_w", nkv), heads(
        "v_proj_w", nkv)
    # query head h attends KV head h // (nh / nkv)
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    ctx = jax.checkpoint(functools.partial(
        _attend, scale=1.0 / math.sqrt(hd), quant=quant))(q, k, v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, nh * hd)
    if cfg["use_gqa_gate"] and _fault(cfg) != "attn_gate_left_out":
        ctx = ctx * jax.nn.sigmoid(_mm(u, p[pre + "g_proj_w"], quant))
    return _mm(ctx, p[pre + "o_proj_w"], quant), jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------

def route(xt, router_w, router_bias, cfg):
    """(idx [T, k], weights [T, k]) over all `experts_total` experts."""
    scores = jax.nn.sigmoid(_mm(xt, router_w))
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(scores + router_bias),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"]


def routed_experts(xt, p, pre, cfg, quant=None):
    """The held experts' part of sum_k w_k E_{i_k}(x): (out [T, d],
    idx [T, k]). A loop (`lax.scan`) over the held experts, each on every
    token, weighted by a mask."""
    idx, w = route(xt, p[pre + "router_w"], p[pre + "router_bias"], cfg)
    held = cfg.get("expert_offset", 0) + jnp.arange(cfg["n_routed_experts"])
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


def expert_layer(u, p, pre, cfg, quant=None):
    ut = u.reshape(-1, u.shape[-1])
    routed, idx = routed_experts(ut, p, pre, cfg, quant)
    y = routed + swiglu_ffn(ut, p[pre + "shared_gate_w"],
                            p[pre + "shared_up_w"], p[pre + "shared_down_w"],
                            quant)
    return y.reshape(u.shape), idx


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def layer(x, p, n, cfg, quant=None):
    """Published layer n: (x_out, idx of the routed choice, its least log
    decay)."""
    pre, eps = f"l{n}_", cfg["rms_norm_eps"]
    attend = attention if is_softmax_layer(cfg, n) else kda_attention
    mixed, least = attend(rms_norm(x, p[pre + "attn_norm_scale"], eps), p,
                          pre, cfg, quant)
    x = x + mixed
    y, idx = expert_layer(rms_norm(x, p[pre + "ffn_norm_scale"], eps), p,
                          pre, cfg, quant)
    return x + y, idx, jax.lax.stop_gradient(least)


def forward(p, ids, cfg, quant=None):
    """(hidden states after the final norm [B, S, d], the routed choice of
    the first layer [B*S, k], the least log decay of any KDA channel)."""
    x = p["embed_tokens"][ids]
    first_idx, least = None, 0.0
    for n in layers_here(cfg):
        # a layer keeps its input alone for the backward and is recomputed
        x, idx, low = jax.checkpoint(
            functools.partial(layer, n=n, cfg=cfg, quant=quant))(x, p)
        least = jnp.minimum(least, low)
        if first_idx is None:
            first_idx = idx
    return (rms_norm(x, p["final_norm_scale"], cfg["rms_norm_eps"]),
            first_idx, least)


def loss_sum(trained, buffers, ids, labels, cfg, quant=None):
    """(sum of the labelled positions' cross entropies over the rows
    given, (the first layer's routed choice, the least log decay))."""
    x, idx, least = forward({**trained, **buffers}, ids, cfg, quant)
    logits = _mm(x, trained["lm_head_w"], quant)
    keep = labels != IGNORE
    safe = jnp.where(keep, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, safe[..., None], -1)[..., 0]
    return jnp.sum(jnp.where(keep, lse - picked, 0.0)), (idx, least)


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _block_grad_decay(trained, buffers, ids, labels, cfg_key, quant):
    cfg = json.loads(cfg_key)
    (val, (idx, least)), grads = jax.value_and_grad(loss_sum, has_aux=True)(
        trained, buffers, ids, labels, cfg, quant)
    return val, idx, grads, least


def _block_grad(trained, buffers, ids, labels, cfg_key, quant):
    """(loss sum, routed choice, gradients): what the other references'
    `_block_grad` returns."""
    return _block_grad_decay(trained, buffers, ids, labels, cfg_key,
                             quant)[:3]


def follow(cfg: dict, fresh_params, batches: list, rows_per_block: int,
           quant=None) -> dict:
    """Train `len(batches)` Adam steps from `fresh_params()` (a call that
    makes the seeded weights anew, so that no second copy of them has to
    live through the steps), each batch a dict of host arrays `ids` [B, S]
    and `labels` [B, S], the gradient taken in blocks of `rows_per_block`
    rows. Adam's moments wait on the host between steps and every leaf is
    updated by itself (the module's docstring says why). Returns each
    step's loss, the routed choice of the first layer at step 1 ([B*S,
    k]), the per-leaf norms of Adam's first moment and of the parameters'
    change after the last step, the first moment itself of
    `vector_leaves`, and `min_log_decay`, the least g any KDA channel had
    in step 1's forward."""
    key = _cfg_key(cfg)
    params, buffers = split_state(cfg, fresh_params())
    vectors_of = set(vector_leaves(cfg))
    moments = {}                      # leaf -> (m, v) as host arrays
    losses, first_idx, least = [], None, 0.0
    moment1, vectors = {}, {}
    for t, batch in enumerate(batches, start=1):
        n_rows = batch["ids"].shape[0]
        labelled = int((batch["labels"] != IGNORE).sum())
        total, grads, idxs = 0.0, None, []
        for lo in range(0, n_rows, rows_per_block):
            sl = slice(lo, lo + rows_per_block)
            val, idx, g, low = _block_grad_decay(
                params, buffers, batch["ids"][sl], batch["labels"][sl], key,
                quant)
            least = min(least, float(low)) if t == 1 else least
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
    # the seeded weights once more (6.7 GB with the trained ones: nothing
    # else is on the chip by now)
    p0, _ = split_state(cfg, fresh_params())
    delta = {k: float(jnp.linalg.norm(params[k] - p0[k])) for k in params}
    return {"losses": losses, "moment1_norms": moment1, "delta_norms": delta,
            "moment1_vectors": vectors, "first_route": first_idx,
            "min_log_decay": least}
