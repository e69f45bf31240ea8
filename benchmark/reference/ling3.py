"""Plain reference of `ling3_flash_vl_ep64_tp2`: one chip's share of the
language model that inclusionAI/Ling-3.0-flash-VL's `config.json` describes
(`model_type` bailing_hybrid, read from its language-only sibling), in
straightforward float32 `jax.numpy`: forward, next-token loss, gradients
and Adam.

No kernels, no AMP, no chunks, no triangular solve, no sort and no grouped
matmul: the gated delta rule is the recurrence itself, one token after
another (`lax.scan`, in blocks of `reference_scan_tokens_per_block` tokens
that keep their opening state for the backward and are run again there, so
that a row of 8,192 fits); attention builds its `[S, S]` mask and scores a
few heads at a time; the group-limited selection is written out plainly;
the routed experts are a loop over the held experts, each run on every
token and weighted by a mask. Matmuls run at precision `highest`. Nothing
is imported from the program. The layer equations (x `[S, d]`, d =
`hidden_size`; H heads of `head_dim` = 128; no bias, no dropout, untied
head):

Published layer n (the layers run here are `first_layer` .. + `layers`):
x <- x + Attn_n(RMSNorm(x)), x <- x + FFN_n(RMSNorm(x)), eps
`rms_norm_eps`; after the last layer a final RMSNorm, then the head over
the `vocab` rows held. Attn_n is latent attention where (n + 1) %
`layer_group_size` == 0 and KDA elsewhere. FFN_n is a SwiGLU of width
`intermediate_size` for n < `first_k_dense_replace`, the expert layer from
there on.

* KDA (per head h; float32 state S [128, 128], zero at a row's start).
  q~ = u W_q, k~ = u W_k, v~ = u W_v, each [S, H x 128]; each through a
  causal depthwise conv of kernel `short_conv_kernel_size` without bias
  (position t sees t - K + 1 .. t, `conv_w[j]` on position t - (K - 1) + j)
  and then silu (`linear_silu`). q_h = l2norm(q~_h) x 128^-0.5,
  k_h = l2norm(k~_h), l2norm(x) = x / sqrt(sum x^2 + 1e-6); no rotary.
  Decay, a channel: g_t = `kda_lower_bound` x sigmoid(exp(A_log_h) x
  (u_t W_f + dt_bias)) in (-5, 0), alpha_t = exp(g_t) (`kda_safe_gate`;
  W_f full rank: `no_kda_lora`). beta_t = sigmoid(u_t W_beta), a scalar a
  head. Then
      S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t
  o_h <- RMSNorm_128(o_h) (one weight of 128 for all heads) x
  sigmoid(u W_g)_h, one scalar a head (`head_wise`); out = concat_h(o_h) W_o.
* latent attention: q = u W_q -> [H, 192]; [c | k_pe] = u W_kva (512 | 64),
  c <- RMSNorm(c), [k_nope | v] = c W_kvb -> [H, 128 | 128]; k = [k_nope |
  k_pe], k_pe one vector a token shared by the heads; RMSNorm a head with a
  weight of 192 on q and on k (`use_qk_norm`), THEN rotary (theta
  `rope_theta`, interleaved pairs: `rope_interleave`) on the last
  `rotary_dim` = 64 features of q and k; causal softmax of q k^T / sqrt(192)
  times v; o_h <- o_h x sigmoid(u W_g)_h; out = concat_h(o_h) W_o.
* expert layer: s = sigmoid(u W_r) over ALL `experts_total` experts, in
  float32; selection on s + b (b a bias no gradient reaches): the experts in
  `n_group` groups of consecutive ones, a group's score the sum of its two
  highest s + b, the best `topk_group` groups kept, the `num_experts_per_tok`
  highest s + b among their experts; weights s there divided by their sum
  (`norm_topk_prob`) times `routed_scaling_factor`; routed = sum_k w_k
  E_{i_k}(u), each E_i a SwiGLU W_down(silu(W_gate u) * W_up u) of width
  `moe_intermediate_size`; plus one shared expert of the same form and
  width `moe_shared_expert_intermediate_size`. No clamp
  (`expert_swiglu_limit_list[n]` = 0 in every layer run).

This chip holds experts `expert_offset` .. + `num_experts` and heads
`head_offset` .. + `num_attention_heads` of `heads_total`, and adds their
terms only: the projections into heads have the held heads' columns, W_o
their rows. With `num_experts` = `experts_total` and `num_attention_heads`
= `heads_total` the same code is the uncut layer.

Departures from the published model, each also in the configuration file's
`assumed`: the row's config holds image and video token ids and no key of a
vision tower, so none is built; no multi-token head; keys the VL row lacks
are read from the language-only sibling row; the selection bias is held
fixed in a step and seeded as a spread; the form of the safe gate, of the
q / k norm in each kind of layer and of the group's score are the family's
conventions.

`quant` names the control: "fp8" rounds both operands of every product the
configuration runs in bf16 (the projections, the experts, attention, the
head, and q, k, v on their way into the recurrence) to float8_e4m3, the step
below, forward (the backward's products then read the rounded operands and
an unrounded cotangent: rounded too, this model's cotangents pass
float8_e4m3's 448 and the control read NaN, my chip run, PR 36); what the
configuration states in float32 stays as it is. Four faults
are switched from `assumed` (absent in the configuration file):
`kda_state_dtype` "bfloat16" rounds the recurrent state after every token,
`kda_no_delta` leaves the term beta k k^T Diag(alpha) S out,
`no_group_limit` takes the plain top-k of all experts, `kda_heads_kept`
zeroes the KDA layers' output of the heads from that one on.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

IGNORE = -100
ADAM = {"lr": 1e-4, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8}
HEADS_PER_BLOCK = 2       # attention two heads at a time, recomputed in
                          # the backward: [B, 2, S, S] scores, never all
L2_EPS = 1e-6
# the steps dt_bias is the inverse softplus of, as the Mamba family draws
# them
DT_MIN, DT_MAX, DT_FLOOR = 0.001, 0.1, 1e-4


def layers_here(cfg: dict) -> range:
    return range(cfg["first_layer"], cfg["first_layer"] + cfg["layers"])


def is_latent_layer(cfg: dict, n: int) -> bool:
    return (n + 1) % cfg["layer_group_size"] == 0


def is_moe_layer(cfg: dict, n: int) -> bool:
    return n >= cfg["first_k_dense_replace"]


def param_shapes(cfg: dict) -> dict:
    """Every leaf Adam trains."""
    d, nh, hd = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, lora = cfg["v_head_dim"], cfg["kv_lora_rank"]
    held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    fs, kc = (cfg["moe_shared_expert_intermediate_size"],
              cfg["short_conv_kernel_size"])
    s = {"embed_tokens": (cfg["vocab"], d), "final_norm_scale": (d,),
         "lm_head_w": (d, cfg["vocab"])}
    for n in layers_here(cfg):
        p = f"l{n}_"
        s.update({p + "attn_norm_scale": (d,), p + "ffn_norm_scale": (d,),
                  p + "g_proj_w": (d, nh)})
        if is_latent_layer(cfg, n):
            s.update({p + "q_proj_w": (d, nh * (nope + rope)),
                      p + "kv_a_proj_w": (d, lora + rope),
                      p + "kv_a_norm_scale": (lora,),
                      p + "kv_b_proj_w": (lora, nh * (nope + vd)),
                      p + "q_norm_scale": (nope + rope,),
                      p + "k_norm_scale": (nope + rope,),
                      p + "o_proj_w": (nh * vd, d)})
        else:
            for name in "qkv":
                s[p + name + "_proj_w"] = (d, nh * hd)
                s[p + name + "_conv_w"] = (kc, nh * hd)
            s.update({p + "f_proj_w": (d, nh * hd), p + "A_log": (nh,),
                      p + "dt_bias": (nh * hd,), p + "b_proj_w": (d, nh),
                      p + "o_norm_scale": (hd,),
                      p + "o_proj_w": (nh * hd, d)})
        if is_moe_layer(cfg, n):
            s.update({p + "router_w": (d, cfg["experts_total"]),
                      p + "experts_gate_w": (held, d, f),
                      p + "experts_up_w": (held, d, f),
                      p + "experts_down_w": (held, f, d),
                      p + "shared_gate_w": (d, fs),
                      p + "shared_up_w": (d, fs),
                      p + "shared_down_w": (fs, d)})
        else:
            i = cfg["intermediate_size"]
            s.update({p + "mlp_gate_w": (d, i), p + "mlp_up_w": (d, i),
                      p + "mlp_down_w": (i, d)})
    return s


def vector_leaves(cfg: dict) -> list:
    """The leaves whose first moment is compared as a VECTOR and not by its
    norm, in every KDA layer: the three short convolutions' kernels, the
    output norm's scale and `beta`'s projection. The recurrence's state
    reaches each through q, k, v, beta or o, and each gradient is a sum of
    like terms over every position, so its direction is steady from seed to
    seed: an unbiased rounding of the state moves such a leaf's norm by the
    rounding's square and its direction by the rounding itself. (`A_log`
    and `dt_bias` are left out: a head's or a channel's gradient there is a
    sum that nearly cancels, and the sound program's own bf16 products move
    its direction by anything from 0.006 to 0.27 at 2,048 to 4,096 tokens on
    the CPU and by 0.19 to 0.24 at the worst leaf on the chip, where these
    thirty read 0.06 to 0.11: PERF.md section 6, PR 36, which also says what
    the number cannot tell apart.)"""
    return [f"l{n}_{leaf}" for n in layers_here(cfg)
            if not is_latent_layer(cfg, n)
            for leaf in ("q_conv_w", "k_conv_w", "v_conv_w", "o_norm_scale",
                         "b_proj_w")]


def buffer_shapes(cfg: dict) -> dict:
    """The selection bias of every expert layer: state no gradient
    reaches and no optimizer updates."""
    return {f"l{n}_router_bias": (cfg["experts_total"],)
            for n in layers_here(cfg) if is_moe_layer(cfg, n)}


@functools.partial(jax.jit, static_argnums=(2, 3))
def _normal(key, idx, shape, std):
    return std * jax.random.normal(jax.random.fold_in(key, idx), shape,
                                   jnp.float32)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _uniform(key, idx, shape, lo, hi):
    return jax.random.uniform(jax.random.fold_in(key, idx), shape,
                              jnp.float32, lo, hi)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _select_bias(key, idx, total, held, offset, std):
    """A selection bias whose SPREAD is fixed by the configuration and
    whose placement is the seed's: the held experts get the `held` evenly
    spaced quantiles of normal(0, std) in a seeded order, the others
    theirs. So every seed routes about as many tokens to this rank, as
    unevenly over its experts; which expert is the full one differs."""
    def spread(n, k):
        q = (jnp.arange(n, dtype=jnp.float32) + 0.5) / n
        return std * jax.scipy.special.ndtri(q)[jax.random.permutation(k, n)]
    k1, k2 = jax.random.split(jax.random.fold_in(key, idx))
    rest = spread(total - held, k2)
    return jnp.concatenate([rest[:offset], spread(held, k1), rest[offset:]])


def init_leaf(cfg: dict, key, name: str):
    """One seeded leaf, by the rules under `assumed`: matrices normal(0,
    initializer std); norm scales 1; the conv kernels uniform(+-1 /
    sqrt(K)); A_log = log uniform(1, 16); dt_bias the inverse softplus of a
    log-uniform step in [0.001, 0.1] floored at 1e-4; selection biases a
    spread of width select_bias_std. The draw depends on the leaf's place
    among the sorted names, not on the other leaves."""
    shapes = dict(param_shapes(cfg), **buffer_shapes(cfg))
    idx, shape = sorted(shapes).index(name), shapes[name]
    assumed = cfg["assumed"]
    if name.endswith("_scale"):
        return jnp.ones(shape, jnp.float32)
    if name.endswith("router_bias"):
        return _select_bias(key, idx, cfg["experts_total"],
                            cfg["num_experts"], cfg.get("expert_offset", 0),
                            assumed["select_bias_std"])
    if name.endswith("conv_w"):
        bound = 1.0 / math.sqrt(cfg["short_conv_kernel_size"])
        return _uniform(key, idx, shape, -bound, bound)
    if name.endswith("A_log"):
        return jnp.log(_uniform(key, idx, shape, 1.0, 16.0))
    if name.endswith("dt_bias"):
        dt = jnp.maximum(jnp.exp(_uniform(key, idx, shape, math.log(DT_MIN),
                                          math.log(DT_MAX))), DT_FLOOR)
        return dt + jnp.log(-jnp.expm1(-dt))      # softplus^-1(dt)
    return _normal(key, idx, shape, assumed["initializer_std"])


def init_params(cfg: dict, key) -> dict:
    """Every trained leaf and every buffer, seeded."""
    return {name: init_leaf(cfg, key, name)
            for name in sorted(dict(param_shapes(cfg), **buffer_shapes(cfg)))}


FP8_MAX = 448.0           # float8_e4m3fn's largest finite value


def _q(x, quant):
    """x as the control holds it: rounded to float8_e4m3 (values past its
    range at the range's end, not NaN), the gradient passing as if it were
    not: a cotangent is no operand the configuration states a precision
    for, and this model's pass 448."""
    if quant is None:
        return x
    if quant == "fp8":
        low = jnp.clip(x, -FP8_MAX, FP8_MAX).astype(
            jnp.float8_e4m3fn).astype(jnp.float32)
        return x + jax.lax.stop_gradient(low - x)
    raise ValueError(quant)


def _mm(a, b, quant=None):
    return jnp.matmul(_q(a, quant), _q(b, quant),
                      precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


def rope(x, theta, rotary_dim):
    """x [..., S, D]: the pairs (2i, 2i+1) of the LAST `rotary_dim` features
    turn by pos * theta^(-2i / rotary_dim)."""
    s, d = x.shape[-2], x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                           / rotary_dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    keep, turn = x[..., :d - rotary_dim], x[..., d - rotary_dim:]
    a, b = turn[..., 0::2], turn[..., 1::2]
    turned = jnp.stack([a * cos - b * sin, b * cos + a * sin],
                       axis=-1).reshape(turn.shape)
    return jnp.concatenate([keep, turned], axis=-1)


def swiglu_ffn(x, w_gate, w_up, w_down, quant=None):
    return _mm(jax.nn.silu(_mm(x, w_gate, quant)) * _mm(x, w_up, quant),
               w_down, quant)


# ---------------------------------------------------------------------------
# KDA: the gated delta rule
# ---------------------------------------------------------------------------

def causal_conv(x, w):
    """x [B, S, C], w [K, C]: out[t] = sum_j w[j] x[t - (K-1) + j], zeros
    before the row's start."""
    k, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * w[j] for j in range(k))


def delta_rule(q, k, v, g, beta, cfg):
    """The recurrence, token by token. q, k, v, g [B, S, H, 128], beta
    [B, S, H]; g the log of a channel's decay. -> o like v."""
    b, s, h, dk = k.shape
    block = min(cfg["reference_scan_tokens_per_block"], s)
    assumed = cfg["assumed"]
    state_dtype = assumed.get("kda_state_dtype", "float32")

    def step(state, inp):
        qt, kt, vt, gt, bt = inp         # [B, H, 128] x 4, [B, H]
        decayed = jnp.exp(gt)[..., None] * state
        seen = jnp.einsum("bhk,bhkv->bhv", kt, decayed,
                          precision=jax.lax.Precision.HIGHEST)
        if assumed.get("kda_no_delta"):  # a fault the check is held to
            seen = jnp.zeros_like(seen)
        state = decayed + (bt[..., None, None] * kt[..., None]
                           * (vt - seen)[..., None, :])
        if state_dtype != "float32":
            state = state.astype(state_dtype).astype(jnp.float32)
        return state, jnp.einsum("bhk,bhkv->bhv", qt, state,
                                 precision=jax.lax.Precision.HIGHEST)

    @jax.checkpoint
    def run_block(state, inps):
        return jax.lax.scan(step, state, inps)

    def blocks(t):                       # [B, S, ...] -> [S/block, block, B, ...]
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((s // block, block) + t.shape[1:])

    _, os = jax.lax.scan(run_block,
                         jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
                         tuple(blocks(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(os.reshape((s,) + os.shape[2:]), 0, 1)


def kda_attention(u, p, pre, cfg, quant=None):
    nh, hd = cfg["num_attention_heads"], cfg["head_dim"]
    b, s, _ = u.shape

    def conv_heads(name):
        x = _mm(u, p[pre + name + "_proj_w"], quant)
        return jax.nn.silu(causal_conv(x, p[pre + name + "_conv_w"])
                           ).reshape(b, s, nh, hd)

    q, k, v = conv_heads("q"), conv_heads("k"), conv_heads("v")
    q, k = l2_norm(q) * hd ** -0.5, l2_norm(k)
    pre_gate = (_mm(u, p[pre + "f_proj_w"], quant)
                + p[pre + "dt_bias"]).reshape(b, s, nh, hd)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p[pre + "A_log"])[:, None] * pre_gate)
    beta = jax.nn.sigmoid(_mm(u, p[pre + "b_proj_w"], quant))
    o = delta_rule(_q(q, quant), _q(k, quant), _q(v, quant), g, beta, cfg)
    kept = cfg["assumed"].get("kda_heads_kept")
    if kept is not None:    # a fault the check is held to
        o = o.at[:, :, kept:].set(0.0)
    o = rms_norm(o, p[pre + "o_norm_scale"], cfg["rms_norm_eps"])
    o = o * jax.nn.sigmoid(_mm(u, p[pre + "g_proj_w"], quant))[..., None]
    return _mm(o.reshape(b, s, nh * hd), p[pre + "o_proj_w"], quant)


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------

def _attend(q, k, v, scale, quant):
    """Causal softmax attention of a few heads: q, k [B, n, S, dq],
    v [B, n, S, dv]."""
    s = q.shape[2]
    scores = _mm(q, jnp.swapaxes(k, -1, -2), quant) * scale
    seen = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    return _mm(jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), v,
               quant)


def latent_attention(u, p, pre, cfg, quant=None):
    nh, nope, rope_d = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                        cfg["qk_rope_head_dim"])
    vd, lora, eps = cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    b, s, _ = u.shape
    q = _mm(u, p[pre + "q_proj_w"], quant).reshape(b, s, nh, nope + rope_d)
    q = q.transpose(0, 2, 1, 3)                           # [B, nh, S, 192]
    kva = _mm(u, p[pre + "kv_a_proj_w"], quant)
    c, k_pe = kva[..., :lora], kva[..., lora:]
    kv = _mm(rms_norm(c, p[pre + "kv_a_norm_scale"], eps),
             p[pre + "kv_b_proj_w"], quant)
    kv = kv.reshape(b, s, nh, nope + vd).transpose(0, 2, 1, 3)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None], (b, nh, s, rope_d))], -1)
    q = rope(rms_norm(q, p[pre + "q_norm_scale"], eps), cfg["rope_theta"],
             cfg["rotary_dim"])
    k = rope(rms_norm(k, p[pre + "k_norm_scale"], eps), cfg["rope_theta"],
             cfg["rotary_dim"])
    block = jax.checkpoint(functools.partial(
        _attend, scale=1.0 / math.sqrt(nope + rope_d), quant=quant))
    step = min(HEADS_PER_BLOCK, nh)

    def groups(t):                       # [B, nh, S, d] -> [nh/step, B, ..]
        return jnp.moveaxis(t.reshape(b, nh // step, step, s, -1), 1, 0)

    ctx = jax.lax.map(lambda qkv: block(*qkv), (groups(q), groups(k),
                                                groups(v)))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, nh, s, vd)
    ctx = ctx.transpose(0, 2, 1, 3)                       # [B, S, nh, 128]
    ctx = ctx * jax.nn.sigmoid(_mm(u, p[pre + "g_proj_w"], quant))[..., None]
    return _mm(ctx.reshape(b, s, nh * vd), p[pre + "o_proj_w"], quant)


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------

def group_limited(sel, n_group, topk_group):
    """sel [T, E] -> the same with every expert outside the `topk_group`
    best groups at -inf: the experts lie in `n_group` groups of consecutive
    ones, and a group's score is the sum of its two highest entries."""
    t, e = sel.shape
    size = e // n_group
    group_score = jnp.stack(
        [jnp.sum(jnp.sort(sel[:, j * size:(j + 1) * size], axis=1)[:, -2:],
                 axis=1) for j in range(n_group)], axis=1)   # [T, n_group]
    # the rank of each group among a token's groups, ties to the lower index
    order = jnp.argsort(-group_score, axis=1, stable=True)
    rank = jnp.argsort(order, axis=1, stable=True)
    kept = jnp.repeat(rank < topk_group, size, axis=1)
    return jnp.where(kept, sel, -jnp.inf)


def route(xt, router_w, router_bias, cfg):
    """(idx [T, k], weights [T, k]) over all `experts_total` experts."""
    scores = jax.nn.sigmoid(_mm(xt, router_w))
    sel = jax.lax.stop_gradient(scores + router_bias)
    if cfg["n_group"] > 1 and not cfg["assumed"].get("no_group_limit"):
        sel = group_limited(sel, cfg["n_group"], cfg["topk_group"])
    _, idx = jax.lax.top_k(sel, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"]


def routed_experts(xt, p, pre, cfg, quant=None):
    """The held experts' part of sum_k w_k E_{i_k}(x): (out [T, d],
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
    """Published layer n: (x_out, idx of the routed choice or None)."""
    pre, eps = f"l{n}_", cfg["rms_norm_eps"]
    attend = latent_attention if is_latent_layer(cfg, n) else kda_attention
    x = x + attend(rms_norm(x, p[pre + "attn_norm_scale"], eps), p, pre, cfg,
                   quant)
    f = rms_norm(x, p[pre + "ffn_norm_scale"], eps)
    if not is_moe_layer(cfg, n):
        return x + swiglu_ffn(f, p[pre + "mlp_gate_w"], p[pre + "mlp_up_w"],
                              p[pre + "mlp_down_w"], quant), None
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if cfg[key][n] != 0:
            raise ValueError(f"layer {n}: {key} = {cfg[key][n]}, a clamp "
                             "whose form is not in config.json")
    y, idx = expert_layer(f, p, pre, cfg, quant)
    return x + y, idx


def forward(p, ids, cfg, quant=None):
    """(hidden states after the final norm [B, S, d], the routed choice of
    the first expert layer [B*S, k])."""
    x = p["embed_tokens"][ids]
    first_idx = None
    for n in layers_here(cfg):
        # a layer keeps its input alone for the backward and is recomputed
        x, idx = jax.checkpoint(
            functools.partial(layer, n=n, cfg=cfg, quant=quant))(x, p)
        if first_idx is None and idx is not None:
            first_idx = idx
    return rms_norm(x, p["final_norm_scale"], cfg["rms_norm_eps"]), first_idx


def loss_sum(trained, buffers, ids, labels, cfg, quant=None):
    """(sum of the labelled positions' cross entropies over the rows
    given, the first expert layer's routed choice)."""
    x, idx = forward({**trained, **buffers}, ids, cfg, quant)
    logits = _mm(x, trained["lm_head_w"], quant)
    keep = labels != IGNORE
    safe = jnp.where(keep, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, safe[..., None], -1)[..., 0]
    return jnp.sum(jnp.where(keep, lse - picked, 0.0)), idx


def _cfg_key(cfg: dict) -> str:
    """The configuration as a hashable static argument, nested groups
    (`assumed`) included."""
    return json.dumps(cfg, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _block_grad(trained, buffers, ids, labels, cfg_key, quant):
    cfg = json.loads(cfg_key)
    (val, idx), grads = jax.value_and_grad(loss_sum, has_aux=True)(
        trained, buffers, ids, labels, cfg, quant)
    return val, idx, grads


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam(params, m, v, grads, t):
    b1, b2 = ADAM["beta1"], ADAM["beta2"]
    lr_t = ADAM["lr"] * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    params = jax.tree.map(
        lambda p, a, c: p - lr_t * a / (jnp.sqrt(c) + ADAM["eps"]),
        params, m, v)
    return params, m, v


def split_state(cfg: dict, params: dict) -> tuple:
    """(trained leaves, buffers) of what `init_params` made."""
    buffers = {n: params[n] for n in buffer_shapes(cfg)}
    return {n: a for n, a in params.items() if n not in buffers}, buffers


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
