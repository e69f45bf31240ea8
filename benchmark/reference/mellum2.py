"""Plain reference of `mellum2_12b_ep4`: one expert-parallel rank's share of
JetBrains/Mellum2-12B-A2.5B-Instruct (`model_type` mellum) in
straightforward float32 `jax.numpy`: forward, next-token loss, gradients
and Adam.

No kernels, no AMP, no sort and no grouped matmul: attention builds its
`[S, S]` mask and scores a few heads at a time, K and V are repeated to the
query heads by `jnp.repeat`, the routed experts are a loop over the held
experts, each run on every token and weighted by a mask. Matmuls run at
precision `highest`. Nothing is imported from the program. The layer
equations (x `[S, hidden]`, RMSNorm, pre-norm residual blocks, no bias, no
dropout, untied head; layer n is of kind `layer_types[n]`):

* a = RMSNorm(x); q = a W_q -> `num_attention_heads` heads of `head_dim`;
  k = a W_k, v = a W_v -> `num_key_value_heads` heads each.
* rotary positions on all `head_dim` features of q and k, half-split pairs
  (j, j + head_dim / 2): position p turns pair j by p f_j, with
  `rope_parameters[kind]`: "default" f_j = theta^(-2j / head_dim); "yarn"
  (factor F, original length L, beta_fast, beta_slow): c(r) = head_dim
  ln(L / (2 pi r)) / (2 ln theta), low = floor(c(beta_fast)), high =
  ceil(c(beta_slow)), both clamped to 0..head_dim - 1, ramp_j =
  clip((j - low) / (high - low), 0, 1), f_j = (1 - ramp_j) theta^(-2j /
  head_dim) + ramp_j theta^(-2j / head_dim) / F, and cos and sin times
  `attention_factor`.
* query head h attends KV head h // (heads / KV heads): o_h = softmax(q_h
  k^T / sqrt(head_dim) + M) v, M causal and, in a sliding layer, -inf where
  i - j >= `sliding_window`; x = x + concat(o) W_o.
* f = RMSNorm(x); s = softmax(f W_r) over ALL `experts_total` experts; the
  `num_experts_per_tok` largest; their weights s there divided by their sum
  (`norm_topk_prob`); y = sum_k w_k E_{i_k}(f), each E_i a SwiGLU of width
  `moe_intermediate_size`; x = x + y. This rank holds experts
  `expert_offset` .. + `num_experts` and adds their terms only; with
  `num_experts` = `experts_total` the same code is the uncut layer.
* final RMSNorm, head over the `vocab` rows held.

Departures from the published model:

* no multi-token-prediction head, no auxiliary balance loss, no per-head
  q / k normalisation: `config.json` has a key for none of them;
* `config.json` gives head counts and no rule: query head h on KV head
  h // group is grouped-query attention's convention
  (`assumed.kv_head_rule`); nor does it name the router's scoring: softmax
  over all experts, then top-k (`assumed.scoring`);
* `intermediate_size` names no layer of this depth pattern (every
  `mlp_layer_types` entry is sparse) and is not read;
* the loss is the mean over the labelled positions' cross entropy, every
  position but a row's last carrying the next token (label -100 = none).

`quant` names the control: "fp8" rounds both operands of every matmul the
configuration runs in bf16 to float8_e4m3, the step below; the router,
which the configuration states in float32, stays as it is.
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
                          # the backward: [B, 2, S, S] scores, never all 32
SLIDING = "sliding_attention"


def param_shapes(cfg: dict) -> dict:
    """Every leaf Adam trains."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    s = {"embed_tokens": (cfg["vocab"], h), "final_norm_scale": (h,),
         "lm_head_w": (h, cfg["vocab"])}
    for n in range(cfg["layers"]):
        p = f"l{n}_"
        s.update({p + "attn_norm_scale": (h,),
                  p + "q_proj_w": (h, nh * hd),
                  p + "k_proj_w": (h, nkv * hd),
                  p + "v_proj_w": (h, nkv * hd),
                  p + "o_proj_w": (nh * hd, h),
                  p + "ffn_norm_scale": (h,),
                  p + "router_w": (h, cfg["experts_total"]),
                  p + "experts_gate_w": (held, h, f),
                  p + "experts_up_w": (held, h, f),
                  p + "experts_down_w": (held, f, h)})
    return s


def buffer_shapes(cfg: dict) -> dict:
    """No state that no gradient reaches: the router has no bias."""
    return {}


@functools.partial(jax.jit, static_argnums=(2, 3))
def _normal(key, idx, shape, std):
    return std * jax.random.normal(jax.random.fold_in(key, idx), shape,
                                   jnp.float32)


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


def _q(x, quant):
    if quant is None:
        return x
    if quant == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    raise ValueError(quant)


def _mm(a, b, quant=None):
    return jnp.matmul(_q(a, quant), _q(b, quant),
                      precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def rope_frequencies(rope: dict, dim: int) -> np.ndarray:
    """f_j of one kind of layer, j < dim / 2, in float64."""
    j = np.arange(dim // 2, dtype=np.float64)
    freq = float(rope["rope_theta"]) ** (-2.0 * j / dim)
    if rope["rope_type"] == "default":
        return freq
    if rope["rope_type"] != "yarn":
        raise ValueError(rope["rope_type"])

    def c(r):
        return dim * math.log(rope["original_max_position_embeddings"]
                              / (2 * math.pi * r)) / (
            2 * math.log(rope["rope_theta"]))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), dim - 1)
    ramp = np.clip((j - low) / (high - low), 0.0, 1.0)
    return (1.0 - ramp) * freq + ramp * freq / rope["factor"]


def rope(x, rope_cfg: dict):
    """x [..., S, D]: the pairs (j, j + D/2) turn by pos * f_j, cos and sin
    times the kind's `attention_factor` (1 where it has none)."""
    s, d = x.shape[-2], x.shape[-1]
    freq = jnp.asarray(rope_frequencies(rope_cfg, d), jnp.float32)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    scale = rope_cfg.get("attention_factor", 1.0)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def swiglu_ffn(x, w_gate, w_up, w_down, quant=None):
    return _mm(jax.nn.silu(_mm(x, w_gate, quant)) * _mm(x, w_up, quant),
               w_down, quant)


def _attend(q, k, v, scale, window, quant):
    """Softmax attention of a few heads, q, k, v [B, n, S, d]: causal, and
    with `window` over the last `window` keys only."""
    s = q.shape[2]
    scores = _mm(q, jnp.swapaxes(k, -1, -2), quant) * scale
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    seen = i >= j
    if window is not None:
        seen = seen & (i - j < window)
    scores = jnp.where(seen, scores, -jnp.inf)
    return _mm(jax.nn.softmax(scores, axis=-1), v, quant)


def attention(x, p, pre, cfg, kind, quant=None):
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    b, s, _ = x.shape

    def heads(w, n):
        return _mm(x, p[pre + w], quant).reshape(b, s, n, hd).transpose(
            0, 2, 1, 3)                                      # [B, n, S, hd]

    rope_cfg = cfg["rope_parameters"][kind]
    q, k, v = heads("q_proj_w", nh), heads("k_proj_w", nkv), heads(
        "v_proj_w", nkv)
    q, k = rope(q, rope_cfg), rope(k, rope_cfg)
    if cfg["assumed"].get("kv_head_rule", "grouped") == "grouped":
        # query head h attends KV head h // (nh / nkv)
        k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    else:       # a fault the check is held to: every query head on KV head 0
        k, v = (jnp.repeat(t[:, :1], nh, axis=1) for t in (k, v))
    window = cfg["sliding_window"] if kind == SLIDING else None
    block = jax.checkpoint(functools.partial(
        _attend, scale=1.0 / math.sqrt(hd), window=window, quant=quant))
    step = min(HEADS_PER_BLOCK, nh)

    def groups(t):                       # [B, nh, S, d] -> [nh/step, B, ..]
        return jnp.moveaxis(t.reshape(b, nh // step, step, s, -1), 1, 0)

    ctx = jax.lax.map(lambda qkv: block(*qkv), (groups(q), groups(k),
                                                groups(v)))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, nh, s, hd)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, nh * hd)
    return _mm(ctx, p[pre + "o_proj_w"], quant)


def route(xt, router_w, cfg):
    """(idx [T, k], weights [T, k]) over all `experts_total` experts."""
    logits = _mm(xt, router_w)
    if cfg["assumed"].get("scoring", "softmax") == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:                   # a fault the check is held to: sigmoid scores
        scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(scores),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=1, keepdims=True)
    return idx, w


def routed_experts(xt, p, pre, cfg, quant=None):
    """The held experts' part of sum_k w_k E_{i_k}(x): (out [T, h],
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


def layer(x, p, n, cfg, quant=None):
    """One pre-norm block: (x_out, idx of the routed choice)."""
    pre = f"l{n}_"
    eps = cfg["rms_norm_eps"]
    kind = cfg["layer_types"][n]
    x = x + attention(rms_norm(x, p[pre + "attn_norm_scale"], eps), p, pre,
                      cfg, kind, quant)
    f = rms_norm(x, p[pre + "ffn_norm_scale"], eps)
    routed, idx = routed_experts(f.reshape(-1, f.shape[-1]), p, pre, cfg,
                                 quant)
    return x + routed.reshape(x.shape), idx


def forward(p, ids, cfg, quant=None):
    """(hidden states after the final norm [B, S, h], the routed choice of
    the first layer [B*S, k])."""
    x = p["embed_tokens"][ids]
    first_idx = None
    for n in range(cfg["layers"]):
        # a layer keeps its input alone for the backward and is recomputed
        x, idx = jax.checkpoint(
            functools.partial(layer, n=n, cfg=cfg, quant=quant))(x, p)
        if first_idx is None:
            first_idx = idx
    return rms_norm(x, p["final_norm_scale"], cfg["rms_norm_eps"]), first_idx


def loss_sum(params, ids, labels, cfg, quant=None):
    """(sum of the labelled positions' cross entropies over the rows given,
    the first layer's routed choice)."""
    x, idx = forward(params, ids, cfg, quant)
    logits = _mm(x, params["lm_head_w"], quant)
    keep = labels != IGNORE
    safe = jnp.where(keep, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, safe[..., None], -1)[..., 0]
    return jnp.sum(jnp.where(keep, lse - picked, 0.0)), idx


def _cfg_key(cfg: dict) -> str:
    """The configuration as a hashable static argument, nested groups
    (`rope_parameters`, `layer_types`, `assumed`) included."""
    return json.dumps(cfg, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _block_grad(params, ids, labels, cfg_key, quant):
    cfg = json.loads(cfg_key)
    (val, idx), grads = jax.value_and_grad(loss_sum, has_aux=True)(
        params, ids, labels, cfg, quant)
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


def follow(cfg: dict, fresh_params, batches: list, rows_per_block: int,
           quant=None) -> dict:
    """Train `len(batches)` Adam steps from `fresh_params()` (a call that
    makes the seeded weights anew, so that no second copy of them has to
    live through the steps), each batch a dict of host arrays `ids` [B, S]
    and `labels` [B, S], the gradient taken in blocks of `rows_per_block`
    rows. Returns each step's loss, the routed choice of the first layer at
    step 1 ([B*S, k]), and the per-leaf norms of Adam's first moment and of
    the parameters' change after the last step."""
    key = _cfg_key(cfg)
    params = fresh_params()
    # Adam's moments wait on the host while a gradient is taken: beside the
    # gradient program's temporaries the 4.8 GB of them do not fit a 16 GB
    # chip
    m = v = None
    losses, first_idx = [], None
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
    del m, v
    p0 = fresh_params()
    delta = {k: float(jnp.linalg.norm(params[k] - p0[k])) for k in params}
    return {"losses": losses, "moment1_norms": moment1, "delta_norms": delta,
            "first_route": first_idx}
