"""Plain reference of the `bert_base` configuration: encoder, masked-LM
loss, gradients and Adam in straightforward float32 `jax.numpy`.

No kernels, no AMP, no batching tricks, nothing imported from the program.
Matmuls run at precision `highest` (on a TPU a float32 matmul is otherwise
done in bf16 passes). It follows Devlin et al. 2018 as the program's
`models/bert.py` builds it; the departures from the published checkpoint,
which are the program's, are:

* no segment (token-type) embedding and no next-sentence head;
* the MLM decoder is its own hidden x vocab matrix with a bias, without the
  dense + LayerNorm transform and not tied to the word embedding;
* layer-norm epsilon 1e-5;
* the loss is the sum of the labelled positions' cross entropies over ALL
  positions of the batch (label -100 = no label, contributes 0).

Adam is Kingma & Ba 2015 in the form of the end of their section 2:
lr_t = lr sqrt(1 - b2^t) / (1 - b1^t), p -= lr_t m / (sqrt(v) + eps).

Dropout is the published 0.1 at the four places the source has it (after
the embedding norm, on the attention probabilities, after the attention
projection, after the feed-forward), kept values scaled by 1 / (1 - p).
The masks are the reference's own, `jax.random.bernoulli` under `drop_key`:
it knows nothing of the program's random stream, so what is compared with
the program are sums over many masked values (a loss, a leaf's norm),
which two mask streams of one rate agree on to within their noise.

`quant` names the control: "fp8" rounds both operands of every matmul to
float8_e4m3 (the step below the bf16 the configuration states).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

IGNORE = -100
LN_EPS = 1e-5
ADAM = {"lr": 1e-4, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8}


def param_shapes(cfg: dict) -> dict:
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    s = {"word_embedding": (cfg["vocab_size"], h),
         "pos_embedding": (cfg["max_position_embeddings"], h),
         "emb_ln_scale": (h,), "emb_ln_bias": (h,),
         "mlm_head_w": (h, cfg["vocab_size"]),
         "mlm_head_b": (cfg["vocab_size"],)}
    for n in range(cfg["num_hidden_layers"]):
        p = f"enc{n}_"
        s.update({p + "attn_qkv_w": (h, 3 * h), p + "attn_qkv_b": (3 * h,),
                  p + "attn_proj_w": (h, h), p + "attn_proj_b": (h,),
                  p + "ln1_scale": (h,), p + "ln1_bias": (h,),
                  p + "ffn_in_w": (h, i), p + "ffn_in_b": (i,),
                  p + "ffn_out_w": (i, h), p + "ffn_out_b": (h,),
                  p + "ln2_scale": (h,), p + "ln2_bias": (h,)})
    return s


def init_params(cfg: dict, key) -> dict:
    """Seeded weights as BERT initialises them: matrices and embeddings
    truncated normal(0, initializer_range), norms 1 / 0, biases 0. One
    jitted call makes every leaf on the device."""
    std = cfg.get("initializer_range", 0.02)
    out = {}
    for idx, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        if name.endswith("_scale"):
            out[name] = jnp.ones(shape, jnp.float32)
        elif len(shape) == 1:
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = std * jax.random.truncated_normal(
                jax.random.fold_in(key, idx), -2.0, 2.0, shape, jnp.float32)
    return out


def _q(x, quant):
    if quant is None:
        return x
    if quant == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if quant == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    raise ValueError(quant)


def _mm(a, b, quant):
    return jnp.matmul(_q(a, quant), _q(b, quant),
                      precision=jax.lax.Precision.HIGHEST)


def _ln(x, scale, bias):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * scale + bias


def _drop(x, rate, key, site):
    if key is None or not rate:
        return x
    keep = jax.random.bernoulli(jax.random.fold_in(key, site), 1.0 - rate,
                                x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


def loss_sum(params, ids, labels, mask, cfg, quant=None, drop_key=None):
    """Sum over the rows given of the labelled positions' cross entropy
    (the caller divides by the number of positions of the whole batch).
    `drop_key` None: no dropout."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = h // nh
    p_hid = cfg["hidden_dropout_prob"]
    p_att = cfg["attention_probs_dropout_prob"]
    b, s = ids.shape
    x = params["word_embedding"][ids] + params["pos_embedding"][:s][None]
    x = _ln(x, params["emb_ln_scale"], params["emb_ln_bias"])
    x = _drop(x, p_hid, drop_key, 0)
    bias = None if mask is None else ((mask - 1.0) * 1e9)[:, None, None, :]
    for n in range(cfg["num_hidden_layers"]):
        p = f"enc{n}_"
        qkv = _mm(x, params[p + "attn_qkv_w"], quant) + params[p + "attn_qkv_b"]
        q, k, v = (t.reshape(b, s, nh, hd).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        scores = _mm(q, k.transpose(0, 1, 3, 2), quant) / math.sqrt(hd)
        if bias is not None:
            scores = scores + bias
        probs = _drop(jax.nn.softmax(scores, axis=-1), p_att, drop_key,
                      3 * n + 1)
        ctx = _mm(probs, v, quant)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h)
        proj = _mm(ctx, params[p + "attn_proj_w"], quant) \
            + params[p + "attn_proj_b"]
        proj = _drop(proj, p_hid, drop_key, 3 * n + 2)
        x = _ln(x + proj, params[p + "ln1_scale"], params[p + "ln1_bias"])
        f = jax.nn.gelu(_mm(x, params[p + "ffn_in_w"], quant)
                        + params[p + "ffn_in_b"], approximate=False)
        f = _mm(f, params[p + "ffn_out_w"], quant) + params[p + "ffn_out_b"]
        f = _drop(f, p_hid, drop_key, 3 * n + 3)
        x = _ln(x + f, params[p + "ln2_scale"], params[p + "ln2_bias"])
    logits = _mm(x, params["mlm_head_w"], quant) + params["mlm_head_b"]
    keep = labels != IGNORE
    safe = jnp.where(keep, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, safe[..., None], -1)[..., 0]
    return jnp.sum(jnp.where(keep, lse - picked, 0.0))


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _block_grad(params, ids, labels, mask, drop_key, cfg_key, quant):
    cfg = dict(cfg_key)
    return jax.value_and_grad(loss_sum)(params, ids, labels, mask, cfg, quant,
                                        drop_key)


@jax.jit
def _adam(params, m, v, grads, t):
    b1, b2 = ADAM["beta1"], ADAM["beta2"]
    lr_t = ADAM["lr"] * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    params = jax.tree.map(
        lambda p, a, c: p - lr_t * a / (jnp.sqrt(c) + ADAM["eps"]),
        params, m, v)
    return params, m, v


def follow(cfg: dict, params: dict, batches: list, rows_per_block: int,
           quant=None, shard=None, drop_key=None) -> dict:
    """Train `len(batches)` Adam steps from `params`, each batch a dict of
    host arrays `ids` [B,S], `labels` [B,S], `mask` [B,S] or None, taking
    the gradient in blocks of `rows_per_block` rows so that the float32
    activations fit beside the program's state. `shard`, if given, places
    a block's rows (a data-parallel split over the chips of the cell).
    `drop_key` seeds the dropout masks, others for every step and block;
    None trains without dropout.
    Returns each step's loss and the per-leaf norms of Adam's first moment
    and of the parameters' change after the last step."""
    cfg_key = tuple(sorted((k, v) for k, v in cfg.items()
                           if isinstance(v, (int, float, str))))
    put = shard or (lambda a: a)
    p0 = params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses = []
    for t, batch in enumerate(batches, start=1):
        n_rows, seq = batch["ids"].shape
        total, grads = 0.0, None
        for lo in range(0, n_rows, rows_per_block):
            sl = slice(lo, lo + rows_per_block)
            mask = None if batch.get("mask") is None else put(batch["mask"][sl])
            key = None if drop_key is None else jax.random.fold_in(
                jax.random.fold_in(drop_key, t), lo)
            val, g = _block_grad(params, put(batch["ids"][sl]),
                                 put(batch["labels"][sl]), mask, key,
                                 cfg_key, quant)
            total = total + val
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        scale = 1.0 / (n_rows * seq)
        grads = jax.tree.map(lambda g: g * scale, grads)
        losses.append(float(total) * scale)
        params, m, v = _adam(params, m, v, grads, float(t))
    return {"losses": losses,
            "moment1_norms": {k: float(jnp.linalg.norm(a))
                              for k, a in m.items()},
            "delta_norms": {k: float(jnp.linalg.norm(params[k] - p0[k]))
                            for k in params}}
