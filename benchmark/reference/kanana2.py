"""Plain reference of `kanana2_30b_a3b_ep8`: one expert-parallel rank's
share of kakaocorp/kanana-2-30b-a3b-instruct-2601 (`model_type`
deepseek_v3, `q_lora_rank` null) in straightforward float32 `jax.numpy`:
forward, next-token loss, gradients and Adam.

No kernels, no AMP, no sort and no grouped matmul: the routed experts are
a loop over the held experts, each run on every token and weighted by a
mask. Matmuls run at precision `highest`. Nothing is imported from the
program. The layer equations (T tokens, RMSNorm, pre-norm residual blocks,
no bias, no dropout, untied head):

* latent attention, every layer: q = h W_q -> [T, nh, qk_nope + qk_rope];
  h W_kva -> [T, kv_lora_rank + qk_rope] = c | k_pe (k_pe one vector a
  token, shared by all heads); RMSNorm(c) W_kvb -> [T, nh, qk_nope + v] =
  k_nope | v; RoPE (theta `rope_theta`) on q_pe and k_pe over interleaved
  pairs; q = [q_nope | q_pe], k = [k_nope | k_pe]; causal softmax of
  q k^T / sqrt(qk_nope + qk_rope), times v; o = concat_heads W_o.
* layers < `first_k_dense_replace`: SwiGLU W_down(silu(W_gate x) * W_up x)
  at `intermediate_size`.
* the other layers: s = sigmoid(x W_g) over ALL `experts_total` experts;
  the `num_experts_per_tok` experts are the top of s + b (b: a buffer no
  gradient reaches); their weights are s there (without b), divided by
  their sum (`norm_topk_prob`), times `routed_scaling_factor`;
  y = sum_k w_k E_{i_k}(x) + S(x), each E_i a SwiGLU of width
  `moe_intermediate_size` and S one of `n_shared_experts` times that.
  This rank holds experts `expert_offset` .. + `n_routed_experts` and adds
  their terms only; with `n_routed_experts` = `experts_total` the same code
  is the uncut layer.
* embedding and head over the `vocab` rows held.

Departures from the published model:

* b is held fixed during a step (the speed of its balancing update is not
  in `config.json`); no auxiliary balance loss and no multi-token-prediction
  head (neither is in `config.json`);
* RoPE turns the interleaved pairs in place; the published code first moves
  them to the half-split layout, a fixed permutation of q_pe and k_pe alike
  that leaves every q k^T unchanged;
* the loss is the mean over the labelled positions' cross entropy, every
  position but a row's last carrying the next token (label -100 = none).

`quant` names the control: "fp8" rounds both operands of every matmul the
configuration runs in bf16 to float8_e4m3, the step below; the router,
which the configuration states in float32, stays as it is.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

IGNORE = -100
ADAM = {"lr": 1e-4, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8}
HEADS_PER_BLOCK = 4       # attention a few heads at a time, recomputed in
                          # the backward: [B, 4, S, S] scores, never all 32


def _dims(cfg):
    nh = cfg["num_attention_heads"]
    return (cfg["hidden_size"], nh, cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])


def is_moe_layer(cfg: dict, n: int) -> bool:
    return n >= cfg["first_k_dense_replace"]


def param_shapes(cfg: dict) -> dict:
    """Every leaf Adam trains."""
    h, nh, nope, rope, vd, lora = _dims(cfg)
    held, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    s = {"embed_tokens": (cfg["vocab"], h), "final_norm_scale": (h,),
         "lm_head_w": (h, cfg["vocab"])}
    for n in range(cfg["layers"]):
        p = f"l{n}_"
        s.update({p + "attn_norm_scale": (h,),
                  p + "q_proj_w": (h, nh * (nope + rope)),
                  p + "kv_a_proj_w": (h, lora + rope),
                  p + "kv_a_norm_scale": (lora,),
                  p + "kv_b_proj_w": (lora, nh * (nope + vd)),
                  p + "o_proj_w": (nh * vd, h),
                  p + "ffn_norm_scale": (h,)})
        if is_moe_layer(cfg, n):
            fs = f * cfg["n_shared_experts"]
            s.update({p + "router_w": (h, cfg["experts_total"]),
                      p + "experts_gate_w": (held, h, f),
                      p + "experts_up_w": (held, h, f),
                      p + "experts_down_w": (held, f, h),
                      p + "shared_gate_w": (h, fs),
                      p + "shared_up_w": (h, fs),
                      p + "shared_down_w": (fs, h)})
        else:
            i = cfg["intermediate_size"]
            s.update({p + "mlp_gate_w": (h, i), p + "mlp_up_w": (h, i),
                      p + "mlp_down_w": (i, h)})
    return s


def buffer_shapes(cfg: dict) -> dict:
    """The selection bias of every expert layer: state no gradient
    reaches and no optimizer updates."""
    return {f"l{n}_router_bias": (cfg["experts_total"],)
            for n in range(cfg["layers"]) if is_moe_layer(cfg, n)}


@functools.partial(jax.jit, static_argnums=(2, 3))
def _normal(key, idx, shape, std):
    return std * jax.random.normal(jax.random.fold_in(key, idx), shape,
                                   jnp.float32)


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
    """One seeded leaf: matrices normal(0, initializer std), norm scales
    1, selection biases a spread of width select_bias_std that changes
    which experts are selected (both under `assumed`). The draw depends on
    the leaf's place among the sorted names, not on the other leaves."""
    shapes = dict(param_shapes(cfg), **buffer_shapes(cfg))
    idx = sorted(shapes).index(name)
    if name.endswith("_scale"):
        return jnp.ones(shapes[name], jnp.float32)
    if name.endswith("router_bias"):
        return _select_bias(key, idx, cfg["experts_total"],
                            cfg["n_routed_experts"],
                            cfg.get("expert_offset", 0),
                            cfg["assumed"]["select_bias_std"])
    return _normal(key, idx, shapes[name], cfg["assumed"]["initializer_std"])


def init_params(cfg: dict, key) -> dict:
    """Every trained leaf and every buffer, seeded."""
    return {name: init_leaf(cfg, key, name)
            for name in sorted(dict(param_shapes(cfg), **buffer_shapes(cfg)))}


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


def rope(x, theta):
    """x [..., S, D]: the pairs (2i, 2i+1) of D turn by pos * theta^(-2i/D)."""
    s, d = x.shape[-2], x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def swiglu_ffn(x, w_gate, w_up, w_down, quant=None):
    return _mm(jax.nn.silu(_mm(x, w_gate, quant)) * _mm(x, w_up, quant),
               w_down, quant)


def _attend(q, k, v, scale, quant):
    """Causal softmax attention of a few heads: q, k [B, n, S, dq],
    v [B, n, S, dv]."""
    s = q.shape[2]
    scores = _mm(q, jnp.swapaxes(k, -1, -2), quant) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    return _mm(jax.nn.softmax(scores, axis=-1), v, quant)


def attention(x, p, pre, cfg, quant=None):
    h, nh, nope, rope_d, vd, lora = _dims(cfg)
    b, s, _ = x.shape
    q = _mm(x, p[pre + "q_proj_w"], quant).reshape(b, s, nh, nope + rope_d)
    q = q.transpose(0, 2, 1, 3)                           # [B, nh, S, 192]
    kva = _mm(x, p[pre + "kv_a_proj_w"], quant)
    c, k_pe = kva[..., :lora], kva[..., lora:]
    kv = _mm(rms_norm(c, p[pre + "kv_a_norm_scale"], cfg["rms_norm_eps"]),
             p[pre + "kv_b_proj_w"], quant)
    kv = kv.reshape(b, s, nh, nope + vd).transpose(0, 2, 1, 3)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate([q[..., :nope],
                         rope(q[..., nope:], cfg["rope_theta"])], -1)
    k_pe = rope(k_pe[:, None], cfg["rope_theta"])         # [B, 1, S, 64]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (b, nh, s, rope_d))], -1)
    scale = 1.0 / math.sqrt(nope + rope_d)
    block = jax.checkpoint(functools.partial(_attend, scale=scale,
                                             quant=quant))
    step = min(HEADS_PER_BLOCK, nh)

    def groups(t):                       # [B, nh, S, d] -> [nh/step, B, ..]
        return jnp.moveaxis(t.reshape(b, nh // step, step, s, -1), 1, 0)

    ctx = jax.lax.map(lambda qkv: block(*qkv), (groups(q), groups(k),
                                                groups(v)))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, nh, s, vd)   # [B, nh, S, 128]
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, nh * vd)
    return _mm(ctx, p[pre + "o_proj_w"], quant)


def route(xt, router_w, router_bias, cfg, use_bias=True, norm=True,
          scaling=None):
    """(idx [T, k], weights [T, k]) over all `experts_total` experts."""
    scores = jax.nn.sigmoid(_mm(xt, router_w))
    sel = scores + router_bias if use_bias else scores
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(sel),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=1)
    if norm:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    scaling = cfg["routed_scaling_factor"] if scaling is None else scaling
    return idx, w * scaling


def routed_experts(xt, p, pre, cfg, quant=None, **route_kw):
    """The held experts' part of sum_k w_k E_{i_k}(x): (out [T, h],
    idx [T, k]). A loop (`lax.scan`) over the held experts, each on every
    token, weighted by a mask."""
    idx, w = route(xt, p[pre + "router_w"], p[pre + "router_bias"], cfg,
                   **route_kw)
    off = cfg.get("expert_offset", 0)
    held = off + jnp.arange(cfg["n_routed_experts"])
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


def shared_expert(xt, p, pre, quant=None):
    return swiglu_ffn(xt, p[pre + "shared_gate_w"], p[pre + "shared_up_w"],
                      p[pre + "shared_down_w"], quant)


def layer(x, p, n, cfg, quant=None):
    """One pre-norm block: (x_out, idx of the routed choice or None)."""
    pre = f"l{n}_"
    eps = cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, p[pre + "attn_norm_scale"], eps), p, pre,
                      cfg, quant)
    f = rms_norm(x, p[pre + "ffn_norm_scale"], eps)
    if not is_moe_layer(cfg, n):
        return x + swiglu_ffn(f, p[pre + "mlp_gate_w"], p[pre + "mlp_up_w"],
                              p[pre + "mlp_down_w"], quant), None
    ft = f.reshape(-1, f.shape[-1])
    routed, idx = routed_experts(ft, p, pre, cfg, quant)
    y = routed + shared_expert(ft, p, pre, quant)
    return x + y.reshape(x.shape), idx


def forward(p, ids, cfg, quant=None):
    """(hidden states after the final norm [B, S, h], the routed choice of
    the first expert layer [B*S, k])."""
    x = p["embed_tokens"][ids]
    first_idx = None
    for n in range(cfg["layers"]):
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


def _cfg_key(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _block_grad(trained, buffers, ids, labels, cfg_key, quant):
    cfg = dict(cfg_key)
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
    layer at step 1 ([B*S, k]), and the per-leaf norms of Adam's first
    moment and of the parameters' change after the last step."""
    key = _cfg_key(cfg)
    params, buffers = split_state(cfg, fresh_params())
    # Adam's moments wait on the host while a gradient is taken: beside the
    # gradient program's temporaries (5.3 GB at the published widths) the
    # 4.6 GB of them do not fit a 16 GB chip
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
    del m, v
    p0, _ = split_state(cfg, fresh_params())
    delta = {k: float(jnp.linalg.norm(params[k] - p0[k])) for k in params}
    return {"losses": losses, "moment1_norms": moment1, "delta_norms": delta,
            "first_route": first_idx}
