"""Plain reference of `keye_vl2_30b_a3b_ep8`: one expert-parallel rank's share
of Kwai-Keye/Keye-VL-2.0-30B-A3B's language model (`model_type` KeyeVL2) in
straightforward float32 `jax.numpy`: forward, the objective, gradients and
Adam.

No kernels, no AMP: the indexer's scores and the attention are built a
block of queries at a time (`reference_tokens_per_block` queries, so that a
row of 8,192 fits) as plain `[queries, keys]` arrays, the selection is
`jax.lax.top_k`, the attention a masked softmax, the routed experts
`reference/mellum2.py`'s loop over the held experts (called, unedited, with
this share). Matmuls run at precision `highest`. Nothing is imported from
the program. The equations (x `[S, 2048]`, eps 1e-6, no bias, no dropout,
untied head; 48 layers, all alike):

* layer n: a = RMSNorm(x); x = x + Attn_n(a); x = x + Experts_n(RMSNorm(x)).
* rotary positions (`rope_theta` 1e7, `rope_scaling.mrope_section`
  [16, 24, 24]): half-split pairs (j, j + D/2) over all D features of a
  head; pair j turns by pos_c(j)[t] theta^(-2j/D), c(j) the section pair j
  falls in (0 for j < 16, 1 for 16 <= j < 40, 2 for 40 <= j < 64 at D =
  128; a head of 64 has the sections halved, [8, 12, 12]), pos `[3, B, S]`.
  A text row has the three streams equal to its own positions.
* main attention: q = a W_q (32 heads of 128), k = a W_k, v = a W_v (4 KV
  heads), query head h on KV head h // 8, q and k turned; query t attends
  the keys of its selection S_t only: o_t,h = sum_{s in S_t}
  softmax_{s in S_t}(q_t,h . k_s / sqrt(128)) v_s; then W_o.
* indexer (reads a DETACHED: no gradient passes from it into x): qI = a W_qI
  (16 heads of 64), kI = LayerNorm(a W_kI) (one head of 64; scale and bias,
  eps 1e-6), both turned by the rotary rule; w = a W_w 16^-0.5 64^-0.5;
  I_t,s = sum_j w_t,j relu(qI_t,j . kI_s) for s <= t. S_t = the
  min(t + 1, 2048) keys s <= t with the largest I_t,s, ties to the lower s
  (`jax.lax.top_k`).
* experts: softmax over all 128 router scores in float32, top-8, weights
  over their sum, gated experts of width 768; this rank adds the terms of
  the experts it holds (`reference/mellum2.py` `routed_experts`).
* objective: L = L_LM + sum_layers L_I. L_LM: mean next-token cross entropy
  over the vocabulary rows held, every position but a row's last. L_I of a
  layer: mean_t KL(p_t || softmax_{s in S_t} I_t,s), p_t,s = sum_h P_t,h,s
  / 32 the attention's probabilities on S_t, a TARGET (no gradient into q,
  k, v). So the trunk learns from L_LM alone and the indexer from L_I alone.

Departures from the published model, each in the configuration's `assumed`:
the vision tower is not built (text rows); the indexer's equations are
DeepSeek-V3.2-Exp's lightning indexer (config.json names its sizes only);
`q_chunk_size` / `kv_chunk_size` are read as tiles of an implementation and
change no number; the objective's L_I is that method's sparse training
stage.

`quant` names the control: "fp8" rounds both operands of every matmul the
configuration runs in bf16 to float8_e4m3 (the indexer's projections and
score products among them); router, norms and the float32 parts stay.
`cfg["assumed"]["fault"]` names one thing done wrong, for the controls that
`correct` must fail: "selection_ignored", "lowest_selected",
"index_loss_left_out", "index_input_attached".
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import mellum2
from .mellum2 import ADAM, IGNORE, _adam, _mm, _normal, rms_norm

__all__ = ["ADAM", "IGNORE", "follow", "init_leaf", "init_params",
           "param_shapes", "buffer_shapes"]


def param_shapes(cfg: dict) -> dict:
    """Every leaf Adam trains."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    sa = cfg["sa_config"]
    ih, ihd = sa["indexer_num_heads"], sa["indexer_head_dim"]
    s = {"embed_tokens": (cfg["vocab"], h), "final_norm_scale": (h,),
         "lm_head_w": (h, cfg["vocab"])}
    for n in range(cfg["layers"]):
        p = f"l{n}_"
        s.update({p + "attn_norm_scale": (h,),
                  p + "q_proj_w": (h, nh * hd),
                  p + "k_proj_w": (h, nkv * hd),
                  p + "v_proj_w": (h, nkv * hd),
                  p + "o_proj_w": (nh * hd, h),
                  p + "indexer_q_w": (h, ih * ihd),
                  p + "indexer_k_w": (h, ihd),
                  p + "indexer_k_norm_scale": (ihd,),
                  p + "indexer_k_norm_bias": (ihd,),
                  p + "indexer_head_w": (h, ih),
                  p + "ffn_norm_scale": (h,),
                  p + "router_w": (h, cfg["experts_total"]),
                  p + "experts_gate_w": (held, h, f),
                  p + "experts_up_w": (held, h, f),
                  p + "experts_down_w": (held, f, h)})
    return s


def buffer_shapes(cfg: dict) -> dict:
    """No state that no gradient reaches."""
    return {}


def indexer_leaves(cfg: dict) -> list:
    return sorted(n for n in param_shapes(cfg) if "indexer_" in n)


def vector_leaves(cfg: dict) -> list:
    """The leaves whose first moment is compared as a VECTOR and not by its
    norm: the scale of every layer's attention norm, the one leaf both the
    attention and the indexer read the layer's input through. A gradient
    that leaks from the indexer's loss into its input turns this leaf's
    direction before it moves any norm."""
    return [f"l{n}_attn_norm_scale" for n in range(cfg["layers"])]


def init_leaf(cfg: dict, key, name: str):
    """One seeded leaf: matrices normal(0, initializer std), norm scales 1,
    the LayerNorm's bias 0. The draw depends on the leaf's place among the
    sorted names, not on the other leaves."""
    shapes = param_shapes(cfg)
    if name.endswith("_scale"):
        return jnp.ones(shapes[name], jnp.float32)
    if name.endswith("_bias"):
        return jnp.zeros(shapes[name], jnp.float32)
    return _normal(key, sorted(shapes).index(name), shapes[name],
                   cfg["assumed"]["initializer_std"])


def init_params(cfg: dict, key) -> dict:
    return {name: init_leaf(cfg, key, name)
            for name in sorted(param_shapes(cfg))}


def sections_for(cfg: dict, dim: int) -> list:
    """`mrope_section` at a head of `dim` features: the published sizes are
    pairs of a head of `head_dim`."""
    full = cfg["rope_scaling"]["mrope_section"]
    return [n * (dim // 2) // sum(full) for n in full]


def rope(x, cfg: dict, positions=None):
    """x [B, (n,) S, D]: pair (j, j + D/2) turns by pos_c(j) theta^(-2j/D);
    `positions` [3, B, S], None: the row's own positions in every stream."""
    s, d = x.shape[-2], x.shape[-1]
    j = np.arange(d // 2, dtype=np.float64)
    freq = jnp.asarray(float(cfg["rope_theta"]) ** (-2.0 * j / d),
                       jnp.float32)
    if positions is None:
        ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None]
    else:
        stream = np.repeat(np.arange(3), sections_for(cfg, d))
        ang = jnp.moveaxis(jnp.asarray(positions, jnp.float32), 0,
                           -1)[..., stream] * freq       # [B, S, D/2]
        if x.ndim == 4:
            ang = ang[:, None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def index_scores(qi, ki, w, lo):
    """I [B, bq, S] of the queries lo .. lo + bq: qi [B, H, bq, D], ki
    [B, S, D], w [B, bq, H]; -inf above the diagonal."""
    prod = jnp.einsum("bhtd,bsd->bhts", qi, ki,
                      precision=jax.lax.Precision.HIGHEST)
    scores = jnp.einsum("bth,bhts->bts", w, jax.nn.relu(prod),
                        precision=jax.lax.Precision.HIGHEST) + 0.0
    t = lo + jnp.arange(qi.shape[2])[:, None]
    return jnp.where(jnp.arange(ki.shape[1])[None, :] <= t, scores, -jnp.inf)


def select_keys(scores, topk: int, lowest: bool = False):
    """[B, bq, S] bool: the `topk` largest finite scores of each query
    (every key where it has fewer), ties to the lower s. `lowest` (a fault):
    the smallest in their place."""
    s = scores.shape[-1]
    ranked = jnp.where(jnp.isfinite(scores), -scores, -jnp.inf) \
        if lowest else scores
    vals, idx = jax.lax.top_k(ranked, min(topk, s))
    b, t = jnp.meshgrid(jnp.arange(scores.shape[0]),
                        jnp.arange(scores.shape[1]), indexing="ij")
    return jnp.zeros(scores.shape, bool).at[
        b[..., None], t[..., None], idx].set(jnp.isfinite(vals))


def _attend_block(q, k, v, qi, ki, w, lo, cfg, quant):
    """One block of queries lo .. lo + bq of one layer: (context [B, nh, bq,
    hd], the block's sum of KL(p_t || softmax_S I_t), its selection
    [B, bq, S])."""
    hd = cfg["head_dim"]
    fault = cfg["assumed"].get("fault")
    scores = index_scores(qi, ki, w, lo)
    causal = jnp.isfinite(scores)
    if fault == "selection_ignored":
        sel = causal
    else:
        sel = select_keys(jax.lax.stop_gradient(scores),
                          cfg["sa_config"]["topk"],
                          lowest=fault == "lowest_selected")
    logits = _mm(q, jnp.swapaxes(k, -1, -2), quant) / math.sqrt(hd)
    probs = jax.nn.softmax(jnp.where(sel[:, None], logits, -jnp.inf), -1)
    ctx = _mm(probs, v, quant)
    p = jax.lax.stop_gradient(jnp.mean(probs, axis=1))       # [B, bq, S]
    logq = jax.nn.log_softmax(jnp.where(sel, scores, -jnp.inf), axis=-1)
    on = sel & (p > 0)
    kl = jnp.where(on, p * (jnp.log(jnp.where(on, p, 1.0))
                            - jnp.where(on, logq, 0.0)), 0.0)
    return ctx, jnp.sum(kl), sel


def attention(a, p, pre, cfg, positions, quant=None):
    """(Attn(a) [B, S, h], the layer's sum over queries of KL, its
    selection [B, S, S])."""
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    sa = cfg["sa_config"]
    ih, ihd = sa["indexer_num_heads"], sa["indexer_head_dim"]
    b, s, _ = a.shape

    def heads(x, w, n, width):
        return _mm(x, p[pre + w], quant).reshape(b, s, n, width).transpose(
            0, 2, 1, 3)                                    # [B, n, S, width]

    q = rope(heads(a, "q_proj_w", nh, hd), cfg, positions)
    k = rope(heads(a, "k_proj_w", nkv, hd), cfg, positions)
    v = heads(a, "v_proj_w", nkv, hd)
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    ai = a if cfg["assumed"].get("fault") == "index_input_attached" \
        else jax.lax.stop_gradient(a)
    qi = rope(heads(ai, "indexer_q_w", ih, ihd), cfg, positions)
    ki = rope(layer_norm(_mm(ai, p[pre + "indexer_k_w"], quant),
                         p[pre + "indexer_k_norm_scale"],
                         p[pre + "indexer_k_norm_bias"],
                         cfg["assumed"]["indexer_norm_eps"]), cfg, positions)
    w = _mm(ai, p[pre + "indexer_head_w"], quant) * (ih ** -0.5
                                                     * ihd ** -0.5)
    bq = min(cfg["reference_tokens_per_block"], s)
    while s % bq:
        bq -= 1
    block = jax.checkpoint(functools.partial(_attend_block, cfg=cfg,
                                             quant=quant))

    def one(args):
        qb, qib, wb, lo = args
        return block(qb, k, v, qib, ki, wb, lo)

    def blocks(t, axis):            # the query axis in blocks, blocks first
        shape = t.shape[:axis] + (s // bq, bq) + t.shape[axis + 1:]
        return jnp.moveaxis(t.reshape(shape), axis, 0)

    ctx, kl, sel = jax.lax.map(one, (blocks(q, 2), blocks(qi, 2),
                                     blocks(w, 1), jnp.arange(0, s, bq)))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(b, nh, s, hd)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, nh * hd)
    sel = jnp.moveaxis(sel, 0, 1).reshape(b, s, s)
    return _mm(ctx, p[pre + "o_proj_w"], quant), jnp.sum(kl), sel


def layer(x, p, n, cfg, positions=None, quant=None):
    """One pre-norm block: (x_out, the routed choice, the sum over queries
    of the indexer's KL, the selection)."""
    pre = f"l{n}_"
    eps = cfg["rms_norm_eps"]
    attended, kl, sel = attention(
        rms_norm(x, p[pre + "attn_norm_scale"], eps), p, pre, cfg, positions,
        quant)
    x = x + attended
    f = rms_norm(x, p[pre + "ffn_norm_scale"], eps)
    routed, idx = mellum2.routed_experts(f.reshape(-1, f.shape[-1]), p, pre,
                                         cfg, quant)
    return x + routed.reshape(x.shape), idx, kl, sel


def loss_parts(params, ids, labels, cfg, quant=None, positions=None):
    """(sum of the labelled positions' cross entropies, each layer's sum
    over queries of the indexer's KL [layers], the first layer's routed
    choice, its selection) over the rows given."""
    x = params["embed_tokens"][ids]
    kls, first = [], None
    for n in range(cfg["layers"]):
        # a layer keeps its input alone for the backward and is recomputed
        x, idx, kl, sel = jax.checkpoint(functools.partial(
            layer, n=n, cfg=cfg, positions=positions, quant=quant))(x, params)
        kls.append(kl)
        if first is None:
            first = (idx, sel)
    x = rms_norm(x, params["final_norm_scale"], cfg["rms_norm_eps"])
    logits = _mm(x, params["lm_head_w"], quant)
    keep = labels != IGNORE
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, jnp.where(keep, labels, 0)[..., None],
                                 -1)[..., 0]
    return (jnp.sum(jnp.where(keep, lse - picked, 0.0)), jnp.stack(kls),
            *first)


def objective(params, ids, labels, labelled, queries, cfg, quant=None,
              positions=None):
    """These rows' part of L = L_LM + sum_layers L_I, the means taken over
    `labelled` positions and `queries` queries of the whole batch."""
    ce, kls, idx, sel = loss_parts(params, ids, labels, cfg, quant, positions)
    index = kls / queries
    if cfg["assumed"].get("fault") == "index_loss_left_out":
        index = jax.lax.stop_gradient(index)
    return ce / labelled + jnp.sum(index), (index, idx, sel)


def _cfg_key(cfg: dict) -> str:
    return json.dumps(cfg, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _block_grad(params, ids, labels, labelled, queries, cfg_key, quant,
                positions=None):
    """((these rows' part of L, (each layer's part of L_I, the first layer's
    routed choice and selection)), the gradient of that part)."""
    cfg = json.loads(cfg_key)
    return jax.value_and_grad(objective, has_aux=True)(
        params, ids, labels, labelled, queries, cfg, quant, positions)


def follow(cfg: dict, fresh_params, batches: list, rows_per_block: int,
           quant=None) -> dict:
    """Train `len(batches)` Adam steps from `fresh_params()`, each batch a
    dict of host arrays `ids` [B, S] and `labels` [B, S] (and optionally
    `positions` [3, B, S]), the gradient taken in blocks of `rows_per_block`
    rows. Returns each step's loss L, each step's L_I a layer, the routed
    choice ([B*S, k]) and the selection ([B, S, S] bool) of the first layer
    at step 1, the per-leaf norms of Adam's first moment and of the
    parameters' change after the last step, and the first moment itself of
    `vector_leaves`."""
    key = _cfg_key(cfg)
    params = fresh_params()
    m = v = None
    losses, index_losses, first_idx, first_sel = [], [], None, None
    for t, batch in enumerate(batches, start=1):
        n_rows, s = batch["ids"].shape
        labelled = float((batch["labels"] != IGNORE).sum())
        total, index, grads, idxs, sels = 0.0, 0.0, None, [], []
        for lo in range(0, n_rows, rows_per_block):
            sl = slice(lo, lo + rows_per_block)
            pos = batch.get("positions")
            (val, (ind, idx, sel)), g = _block_grad(
                params, batch["ids"][sl], batch["labels"][sl], labelled,
                float(n_rows * s), key, quant,
                None if pos is None else pos[:, sl])
            total, index = total + float(val), index + np.asarray(ind)
            idxs.append(idx)
            sels.append(np.asarray(sel))
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        if first_idx is None:
            first_idx = jnp.concatenate(idxs, axis=0)
            first_sel = np.concatenate(sels, axis=0)
        losses.append(total)
        index_losses.append([float(x) for x in index])
        if m is None:
            m = jax.tree.map(jnp.zeros_like, params)
            v = jax.tree.map(jnp.zeros_like, params)
        params, m, v = _adam(params, jax.device_put(m), jax.device_put(v),
                             grads, float(t))
        del grads
        if t < len(batches):
            # Adam's moments wait on the host while a gradient is taken
            m, v = jax.device_get((m, v))
    moment1 = {k: float(jnp.linalg.norm(a)) for k, a in m.items()}
    vectors = {k: np.asarray(m[k], np.float32) for k in vector_leaves(cfg)}
    del m, v
    p0 = fresh_params()
    delta = {k: float(jnp.linalg.norm(params[k] - p0[k])) for k in params}
    return {"losses": losses, "index_losses": index_losses,
            "moment1_norms": moment1, "delta_norms": delta,
            "moment1_vectors": vectors, "first_route": first_idx,
            "first_select": first_sel}
