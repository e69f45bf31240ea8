"""Plain reference of `nemotron_twotower_30b_a3b_ep16`: one expert-parallel
rank's share of the language model that
nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16's `config.json` describes
(`model_type` nemotron_h), in straightforward float32 `jax.numpy`: forward,
next-token loss, gradients and Adam.

No kernels, no AMP, no chunks, no sort and no grouped matmul: the selective
scan is the recurrence itself, one token after another (`lax.scan`, in
blocks of `reference_scan_tokens_per_block` tokens that keep their opening
state for the backward and are run again there, so that a row of 8,192
fits); attention builds its `[S, S]` mask and scores a few heads at a time,
K and V repeated to the query heads by `jnp.repeat`; the routed experts are
a loop over the held experts, each run on every token and weighted by a
mask. Matmuls run at precision `highest`. Nothing is imported from the
program. The layer equations (x `[S, d]`, d = `hidden_size`; no dropout,
untied head):

Every layer n: x <- x + Mixer_n(RMSNorm(x)), eps `layer_norm_epsilon`, the
kind of Mixer_n the n-th letter of `hybrid_override_pattern`; after the last
layer a final RMSNorm, then the head over the `vocab` rows held.

* `M`, Mamba-2. H = `mamba_num_heads`, P = `mamba_head_dim`, d_in = H P,
  G = `n_groups`, N = `ssm_state_size`, K = `conv_kernel`.
  [z | xBC | dt] = u W_in, widths d_in | d_in + 2 G N | H, no bias.
  xBC <- silu(conv(xBC) + b), conv depthwise and causal: position t sees
  t - K + 1 .. t, `conv_w[j]` on position t - (K - 1) + j. Split x [H, P],
  B [G, N], C [G, N]; head h reads group h // (H / G).
  dt <- softplus(dt + dt_bias) (`time_step_limit` (0, inf): no clamp),
  A = -exp(A_log) [H]. Per head, from a zero state [P, N]:
      h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t
      y_t = h_t C_t + D x_t
  y <- GroupRMSNorm(y * silu(z)): over each of the G slices of d_in / G
  features, times a weight of d_in, eps `layer_norm_epsilon`. out = y W_out.
* `E`. s = sigmoid(u W_r) over ALL `experts_total` experts, in float32; the
  `num_experts_per_tok` largest of s + b (b a selection bias no gradient
  reaches; `n_group` = `topk_group` = 1: no grouping); their weights s
  there divided by their sum (`norm_topk_prob`) and times
  `routed_scaling_factor`; routed = sum_k w_k E_{i_k}(u), each
  E_i(u) = W_down relu(W_up u)^2 of width `moe_intermediate_size`; plus
  one shared expert of the same form and width
  `moe_shared_expert_intermediate_size` on every token. This rank holds
  experts `expert_offset` .. + `n_routed_experts` and adds their terms
  only; with `n_routed_experts` = `experts_total` the same code is the
  uncut layer.
* `*`. q = u W_q -> `num_attention_heads` heads of `head_dim`, k = u W_k,
  v = u W_v -> `num_key_value_heads` heads; query head h attends KV head
  h // (heads / KV heads): o_h = softmax(q_h k^T / sqrt(head_dim) + M) v, M
  causal; out = concat(o) W_o. No bias, NO rotary positions.

Departures from the published model, each also in the configuration file's
`assumed`:

* `described_as` speaks of a second (denoiser) tower with adaLN, cross-tower
  conditioning, bidirectional in-block attention and decoding by block
  diffusion. `config.json` has a key for none of it and their equations
  cannot be written from what is there: none of it is here. This is the
  tower `config.json` describes, trained with next-token loss.
* d_in = `mamba_num_heads` x `mamba_head_dim` = 4096, as the family's code
  reads it; `expand` (2, which would give 5376) is not read.
* no rotary positions in the attention layers: `rope_theta` and
  `partial_rotary_factor` are kept as published and not read.
* the selection bias is held fixed (its update rule is not in
  `config.json`) and seeded as a spread of width `select_bias_std`.
* the loss is the mean over the labelled positions' cross entropy, every
  position but a row's last carrying the next token (label -100 = none); no
  auxiliary balance loss.

`quant` names the control: "fp8" rounds both operands of every product the
configuration runs in bf16 (the projections, the experts, attention, the
head, and x, B, C on their way into the scan) to float8_e4m3, the step
below; what the configuration states in float32 stays as it is. Three
faults are switched from `assumed` (absent in the configuration file):
`scan_state_dtype` "bfloat16" rounds the recurrent state after every token,
`ssm_heads_kept` zeroes the scan's output of the heads from that one on,
`float32_parts` "bfloat16" rounds what the file states in float32 (norms,
router, conv, dt, the decays, the states).
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

IGNORE = -100
ADAM = {"lr": 1e-4, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8}
HEADS_PER_BLOCK = 2       # attention two heads at a time, recomputed in
                          # the backward: [B, 2, S, S] scores, never all 32
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def layer_kinds(cfg: dict) -> str:
    return cfg["hybrid_override_pattern"][:cfg["layers"]]


def ssm_dims(cfg: dict) -> tuple:
    """(H, P, G, N, d_in, width of xBC)"""
    h, p, g, n = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                  cfg["n_groups"], cfg["ssm_state_size"])
    return h, p, g, n, h * p, h * p + 2 * g * n


def param_shapes(cfg: dict) -> dict:
    """Every leaf Adam trains."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    held, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = cfg["moe_shared_expert_intermediate_size"]
    hm, _, _, _, d_in, xbc = ssm_dims(cfg)
    s = {"embed_tokens": (cfg["vocab"], d), "final_norm_scale": (d,),
         "lm_head_w": (d, cfg["vocab"])}
    for n, kind in enumerate(layer_kinds(cfg)):
        p = f"l{n}_"
        s[p + "norm_scale"] = (d,)
        if kind == MAMBA:
            s.update({p + "in_proj_w": (d, d_in + xbc + hm),
                      p + "conv_w": (cfg["conv_kernel"], xbc),
                      p + "conv_b": (xbc,), p + "dt_bias": (hm,),
                      p + "A_log": (hm,), p + "D": (hm,),
                      p + "ssm_norm_scale": (d_in,),
                      p + "out_proj_w": (d_in, d)})
        elif kind == EXPERTS:
            s.update({p + "router_w": (d, cfg["experts_total"]),
                      p + "experts_up_w": (held, d, f),
                      p + "experts_down_w": (held, f, d),
                      p + "shared_up_w": (d, fs),
                      p + "shared_down_w": (fs, d)})
        elif kind == ATTENTION:
            s.update({p + "q_proj_w": (d, nh * hd),
                      p + "k_proj_w": (d, nkv * hd),
                      p + "v_proj_w": (d, nkv * hd),
                      p + "o_proj_w": (nh * hd, d)})
        else:
            raise ValueError(f"hybrid_override_pattern: no kind {kind!r}")
    return s


def buffer_shapes(cfg: dict) -> dict:
    """The selection bias of every expert layer: state no gradient
    reaches and no optimizer updates."""
    return {f"l{n}_router_bias": (cfg["experts_total"],)
            for n, kind in enumerate(layer_kinds(cfg)) if kind == EXPERTS}


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


_OUT_PROJECTIONS = ("out_proj_w", "o_proj_w", "experts_down_w",
                    "shared_down_w")


def init_leaf(cfg: dict, key, name: str):
    """One seeded leaf, by the rules under `assumed`: matrices normal(0,
    initializer std), the projections back into the residual stream
    divided by sqrt(`num_hidden_layers`) (`rescale_prenorm_residual`); norm
    scales and D 1; the conv kernel and bias uniform(+-1 / sqrt(K));
    A_log = log uniform(1, 16); dt_bias the inverse softplus of a
    log-uniform step in [`time_step_min`, `time_step_max`] floored at
    `time_step_floor`; selection biases a spread of width select_bias_std.
    The draw depends on the leaf's place among the sorted names, not on
    the other leaves."""
    shapes = dict(param_shapes(cfg), **buffer_shapes(cfg))
    idx, shape = sorted(shapes).index(name), shapes[name]
    assumed = cfg["assumed"]
    if name.endswith("_scale") or name.endswith("_D"):
        return jnp.ones(shape, jnp.float32)
    if name.endswith("router_bias"):
        return _select_bias(key, idx, cfg["experts_total"],
                            cfg["n_routed_experts"],
                            cfg.get("expert_offset", 0),
                            assumed["select_bias_std"])
    if name.endswith(("conv_w", "conv_b")):
        bound = 1.0 / math.sqrt(cfg["conv_kernel"])
        return _uniform(key, idx, shape, -bound, bound)
    if name.endswith("A_log"):
        return jnp.log(_uniform(key, idx, shape, 1.0, 16.0))
    if name.endswith("dt_bias"):
        lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
        dt = jnp.maximum(jnp.exp(_uniform(key, idx, shape, lo, hi)),
                         cfg["time_step_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))      # softplus^-1(dt)
    std = assumed["initializer_std"]
    if cfg.get("rescale_prenorm_residual") and name.endswith(_OUT_PROJECTIONS):
        std = std / math.sqrt(cfg["num_hidden_layers"])
    return _normal(key, idx, shape, std)


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


def _stated_f32(x, cfg):
    """x as the configuration states it, float32; under the fault
    `float32_parts` "bfloat16", rounded to that."""
    if cfg["assumed"].get("float32_parts", "float32") == "float32":
        return x
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def rms_norm(x, scale, cfg):
    x = _stated_f32(x, cfg)
    return _stated_f32(
        x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                          + cfg["layer_norm_epsilon"]) * scale, cfg)


def relu2_ffn(x, w_up, w_down, quant=None):
    return _mm(jnp.square(jax.nn.relu(_mm(x, w_up, quant))), w_down, quant)


# ---------------------------------------------------------------------------
# M: the Mamba-2 mixer
# ---------------------------------------------------------------------------

def causal_conv(x, w, b):
    """x [B, S, C], w [K, C], b [C]: out[t] = sum_j w[j] x[t - (K-1) + j] + b,
    zeros before the row's start."""
    k, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * w[j] for j in range(k)) + b


def selective_scan(x, bm, cm, dt, a, d, cfg):
    """The recurrence, token by token. x [B, S, H, P]; bm, cm [B, S, G, N];
    dt [B, S, H] after its softplus; a [H] negative; d [H]. -> y like x."""
    b, s, h, p = x.shape
    per_group = h // bm.shape[2]
    block = min(cfg["reference_scan_tokens_per_block"], s)
    state_dtype = cfg["assumed"].get("scan_state_dtype", "float32")

    def step(state, inp):
        xt, bt, ct, dtt = inp            # [B, H, P], [B, G, N] x 2, [B, H]
        bt, ct = (jnp.repeat(t, per_group, axis=1) for t in (bt, ct))
        decay = _stated_f32(jnp.exp(dtt * a), cfg)
        state = (decay[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        if state_dtype != "float32":
            state = state.astype(state_dtype).astype(jnp.float32)
        state = _stated_f32(state, cfg)
        return state, jnp.sum(state * ct[:, :, None, :], -1) + d[:, None] * xt

    @jax.checkpoint
    def run_block(state, inps):
        return jax.lax.scan(step, state, inps)

    def blocks(t):                       # [B, S, ...] -> [S/block, block, B, ...]
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((s // block, block) + t.shape[1:])

    _, ys = jax.lax.scan(run_block,
                         jnp.zeros((b, h, p, bm.shape[3]), jnp.float32),
                         tuple(blocks(t) for t in (x, bm, cm, dt)))
    return jnp.moveaxis(ys.reshape((s,) + ys.shape[2:]), 0, 1)


def group_rms_norm(y, scale, groups, cfg):
    y = _stated_f32(y, cfg)
    g = y.reshape(y.shape[:-1] + (groups, y.shape[-1] // groups))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                          + cfg["layer_norm_epsilon"])
    return _stated_f32(g.reshape(y.shape) * scale, cfg)


def mamba_mixer(u, p, pre, cfg, quant=None):
    h, hp, g, n, d_in, xbc_w = ssm_dims(cfg)
    b, s, _ = u.shape
    z, xbc, dt = jnp.split(_mm(u, p[pre + "in_proj_w"], quant),
                           [d_in, d_in + xbc_w], axis=-1)
    xbc = _stated_f32(jax.nn.silu(causal_conv(
        _stated_f32(xbc, cfg), _stated_f32(p[pre + "conv_w"], cfg),
        p[pre + "conv_b"])), cfg)
    x, bm, cm = jnp.split(xbc, [d_in, d_in + g * n], axis=-1)
    dt = _stated_f32(jax.nn.softplus(dt + p[pre + "dt_bias"]), cfg)
    y = selective_scan(_q(x, quant).reshape(b, s, h, hp),
                       _q(bm, quant).reshape(b, s, g, n),
                       _q(cm, quant).reshape(b, s, g, n), dt,
                       -jnp.exp(p[pre + "A_log"]), p[pre + "D"], cfg)
    kept = cfg["assumed"].get("ssm_heads_kept")
    if kept is not None:    # a fault the check is held to
        y = y.at[:, :, kept:].set(0.0)
    y = group_rms_norm(y.reshape(b, s, d_in) * jax.nn.silu(z),
                       p[pre + "ssm_norm_scale"], g, cfg)
    return _mm(y, p[pre + "out_proj_w"], quant)


# ---------------------------------------------------------------------------
# E: routed experts and the shared one
# ---------------------------------------------------------------------------

def route(xt, router_w, router_bias, cfg):
    """(idx [T, k], weights [T, k]) over all `experts_total` experts."""
    scores = jax.nn.sigmoid(_stated_f32(
        _mm(_stated_f32(xt, cfg), _stated_f32(router_w, cfg)), cfg))
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
        up, down, weight = e
        return out + weight[:, None] * relu2_ffn(xt, up, down, quant), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(xt),
        (p[pre + "experts_up_w"], p[pre + "experts_down_w"], w_e))
    return out, idx


def expert_layer(u, p, pre, cfg, quant=None):
    ut = u.reshape(-1, u.shape[-1])
    routed, idx = routed_experts(ut, p, pre, cfg, quant)
    y = routed + relu2_ffn(ut, p[pre + "shared_up_w"],
                           p[pre + "shared_down_w"], quant)
    return y.reshape(u.shape), idx


# ---------------------------------------------------------------------------
# *: attention
# ---------------------------------------------------------------------------

def _attend(q, k, v, scale, quant):
    """Causal softmax attention of a few heads, q, k, v [B, n, S, d]."""
    s = q.shape[2]
    scores = _mm(q, jnp.swapaxes(k, -1, -2), quant) * scale
    seen = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    return _mm(jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), v,
               quant)


def attention(u, p, pre, cfg, quant=None):
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
    block = jax.checkpoint(functools.partial(
        _attend, scale=1.0 / math.sqrt(hd), quant=quant))
    step = min(HEADS_PER_BLOCK, nh)

    def groups(t):                       # [B, nh, S, d] -> [nh/step, B, ..]
        return jnp.moveaxis(t.reshape(b, nh // step, step, s, -1), 1, 0)

    ctx = jax.lax.map(lambda qkv: block(*qkv), (groups(q), groups(k),
                                                groups(v)))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, nh, s, hd)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, nh * hd)
    return _mm(ctx, p[pre + "o_proj_w"], quant)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def layer(x, p, n, cfg, quant=None):
    """x + Mixer_n(RMSNorm(x)): (x_out, idx of the routed choice or None)."""
    pre, kind = f"l{n}_", layer_kinds(cfg)[n]
    u = rms_norm(x, p[pre + "norm_scale"], cfg)
    if kind == MAMBA:
        return x + mamba_mixer(u, p, pre, cfg, quant), None
    if kind == ATTENTION:
        return x + attention(u, p, pre, cfg, quant), None
    y, idx = expert_layer(u, p, pre, cfg, quant)
    return x + y, idx


def forward(p, ids, cfg, quant=None):
    """(hidden states after the final norm [B, S, d], the routed choice of
    the first expert layer [B*S, k])."""
    x = p["embed_tokens"][ids]
    first_idx = None
    for n in range(cfg["layers"]):
        # a layer keeps its input alone for the backward and is recomputed
        x, idx = jax.checkpoint(
            functools.partial(layer, n=n, cfg=cfg, quant=quant))(x, p)
        if first_idx is None and idx is not None:
            first_idx = idx
    return rms_norm(x, p["final_norm_scale"], cfg), first_idx


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
    layer at step 1 ([B*S, k]), and the per-leaf norms of Adam's first
    moment and of the parameters' change after the last step."""
    key = _cfg_key(cfg)
    params, buffers = split_state(cfg, fresh_params())
    # Adam's moments wait on the host while a gradient is taken: beside the
    # gradient program's temporaries the 5.3 GB of them do not fit a 16 GB
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
    del m, v
    p0, _ = split_state(cfg, fresh_params())
    delta = {k: float(jnp.linalg.norm(params[k] - p0[k])) for k in params}
    return {"losses": losses, "moment1_norms": moment1, "delta_norms": delta,
            "first_route": first_idx}
