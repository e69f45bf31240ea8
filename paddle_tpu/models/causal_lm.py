"""What the sparse causal LMs of this directory have in common
(`deepseek_v3.py`, `mellum.py`, `nemotron_h.py`, `ling.py`, `keye.py`,
`lfm2.py`, `solar.py`), written once:
the leaves (a seeded weight, a bias-free projection, an RMS norm, the split
into heads), the token embedding and the next-token loss, the dense and the
shared feed-forward parts, an expert layer around `layers.routed_moe`,
attention on grouped KV heads, the layer loop, the sharding rules they all
carry, and the expert loads' way into the metrics.

Each model file keeps what only it has: its configuration with the
published keys, the mixers of its own, and `decoder_layer`, which says what
mixer and what feed-forward part layer n gets. What differs between the
callers of a function here is an argument of it, every value of which has a
caller; no function here asks which model it builds. A configuration is
read by the keys they all spell alike (`hidden_size`, `seq_len`,
`vocab_size`, `initializer_range`, `head_dim`, `moe_intermediate_size`,
`num_experts_per_tok`, `norm_topk_prob`, `experts_held`, `expert_offset`);
where the published names differ the caller passes the value.

Ops of the Program IR only, through the one `paddle_tpu.layers` module.
Parameters are created in the order the builders always created them:
a checkpoint is loaded by name, but a startup program is run in order.
"""
from __future__ import annotations

import math

from jax.sharding import PartitionSpec as P

from .. import layers
from .. import initializer as I
from ..framework.program import name_scope
from ..layer_helper import ParamAttr
from ..observability import metrics
from ..observability.trace import RecordEvent
from ..parallel.mesh import ShardingRules, moe_sharding_rules


def _w(name, cfg):
    return ParamAttr(name=name,
                     initializer=I.Normal(0.0, cfg.initializer_range))


def _linear(x, size, name, cfg):
    return layers.fc(x, size, num_flatten_dims=2, param_attr=_w(name, cfg),
                     bias_attr=False)


_EPSILON_KEYS = ("rms_norm_eps", "layer_norm_epsilon", "norm_eps")


def _norm(x, name, cfg):
    """RMS norm over the last axis; the epsilon under whichever of the
    published names the configuration has (`rms_norm_eps`;
    `layer_norm_epsilon` in the Nemotron-H family, `norm_eps` in LFM2's)."""
    eps = next(getattr(cfg, key) for key in _EPSILON_KEYS
               if hasattr(cfg, key))
    return layers.rms_norm(x, epsilon=eps, param_attr=ParamAttr(name=name))


def _heads(t, nh, width):
    return layers.transpose(layers.reshape(t, [0, 0, nh, width]),
                            [0, 2, 1, 3])                  # [B, nh, S, w]


def gated_ffn(x, width, pre, cfg):
    """W_down(silu(W_gate x) * W_up x)."""
    return _linear(
        layers.swiglu(_linear(x, width, pre + "gate_w", cfg),
                      _linear(x, width, pre + "up_w", cfg)),
        cfg.hidden_size, pre + "down_w", cfg)


def dense_ffn(x, width, pre, cfg):
    """A dense layer's feed-forward part, `gated_ffn` under the scope
    `ffn.dense` (the shared expert's is `moe.shared`)."""
    with name_scope("ffn.dense"):
        return gated_ffn(x, width, pre, cfg)


def relu2_ffn(x, width, pre, cfg):
    """W_down relu(W_up x)^2."""
    return _linear(layers.relu2(_linear(x, width, pre + "up_w", cfg)),
                   cfg.hidden_size, pre + "down_w", cfg)


def expert_layer(x, cfg, pre, *, experts_total, scoring="sigmoid",
                 select_bias=True, gated=True, routed_scaling=1.0,
                 n_group=1, topk_group=1, latent=None, shared=None,
                 norm_topk_eps=None):
    """(this rank's routed part [+ the shared expert], top_idx,
    expert_load) of one expert layer: a router over ALL `experts_total`
    experts (`scoring` "sigmoid" or "softmax"), with `select_bias` a buffer
    added to the scores for the selection alone, the top
    `num_experts_per_tok` (inside the best `topk_group` of `n_group` groups
    where `n_group` > 1), their weights divided by their sum
    (`norm_topk_prob`; `norm_topk_eps` is added to that sum, None: the
    op's own 1e-20) and times `routed_scaling`; the `experts_held`
    experts from `expert_offset` that this rank holds, with a gate
    (`gated`: W_down(silu(W_gate x) * W_up x)) or without (W_down
    relu(W_up x)^2).

    `latent` (width, into, out_of): the experts live in a latent of that
    width; `into(x)` gives what they read and `out_of(routed)` brings their
    sum back to `hidden_size`, both the caller's ops (called here so that
    its parameters are created after the experts' and before the shared
    expert's); the router reads x. `shared` (ffn, width): a shared expert
    `ffn(x, width, pre + "shared_", cfg)` (`gated_ffn` or `relu2_ffn`) added
    to the routed part under the scope `moe.shared`. The `routed_moe` op
    itself carries `moe.io`: what it does outside the four parts its
    lowering names (the AMP casts of the experts it reads, reshapes)."""
    h, f = cfg.hidden_size, cfg.moe_intermediate_size
    width, into, out_of = latent or (h, None, None)
    held = cfg.experts_held or experts_total

    def experts(name, rows, cols):
        return layers.create_parameter(
            [held, rows, cols], "float32",
            attr=_w(pre + f"experts_{name}_w", cfg))

    gate_w = layers.create_parameter(
        [h, experts_total], "float32", attr=_w(pre + "router_w", cfg))
    bias = None
    if select_bias:
        bias = layers.create_parameter(
            [experts_total], "float32",
            attr=ParamAttr(name=pre + "router_bias", trainable=False,
                           initializer=I.Constant(0.0)))
    gate = experts("gate", width, f) if gated else None
    up, down = experts("up", width, f), experts("down", f, width)
    expert_input = into(x) if latent else None
    with name_scope("moe.io"):
        routed, idx, load = layers.routed_moe(
            x, gate_w, gate, up, down, top_k=cfg.num_experts_per_tok,
            select_bias=bias, routed_scaling=routed_scaling,
            norm_topk=cfg.norm_topk_prob, experts_total=experts_total,
            expert_offset=cfg.expert_offset, scoring=scoring,
            n_group=n_group, topk_group=topk_group,
            expert_input=expert_input, norm_topk_eps=norm_topk_eps)
    if latent:
        routed = out_of(routed)
    if not shared:
        return routed, idx, load
    ffn, shared_width = shared
    with name_scope("moe.shared"):
        return layers.elementwise_add(
            routed, ffn(x, shared_width, pre + "shared_", cfg)), idx, load


def grouped_attention(x, cfg, pre, heads, kv_heads, rotary=None, window=None,
                      selection=None, qk_norm=False, gate=False):
    """`heads` query heads on `kv_heads` KV heads of `head_dim` (query head
    h attends KV head h // group), causal; `rotary` turns q and k (None: no
    rotary positions), `window` keeps the last `window` keys only (None:
    all of them). K and V go to the attention op at their own head count.
    Scopes `attn.proj`, and `attn.attend.window` or `attn.attend.full`.

    `selection`: a learned choice of keys in place of a static window, an
    int8 variable [B, S, S] (`layers.sparse_index`'s `select`) that is 1
    where query t attends key s, the same for all `heads` of a row. The
    result is then (out, target): `target` [B, S, S], the mean over the
    heads of the attention's probabilities on the selected pairs, which the
    indexer that chose them is trained towards; scope `attn.attend.sparse`.
    None: today's ops in today's order.

    `qk_norm`: a per-head norm, q and k each under an RMS norm over a
    head's `head_dim` features (the configuration's epsilon) with ONE
    learned scale of `head_dim` that all the heads of q, or of k, share
    (`q_norm_scale`, `k_norm_scale`), BEFORE the rotary turn; scope
    `attn.qk_norm`. False: no such ops, and today's in today's order.

    `gate`: an element-wise output gate, the heads' outputs times
    sigmoid(x W_gate) (`g_proj_w` [hidden, heads * head_dim], from the
    layer's normed input) before the output projection, inside the scope
    `attn.proj` (the gate's projection, its sigmoid and the product).
    False: no such ops."""
    hd = cfg.head_dim
    turn = rotary or (lambda t: t)

    def normed(t, name):
        if not qk_norm:
            return t
        with name_scope("attn.qk_norm"):
            return _norm(t, pre + name, cfg)

    with name_scope("attn.proj"):
        q = turn(normed(_heads(_linear(x, heads * hd, pre + "q_proj_w", cfg),
                               heads, hd), "q_norm_scale"))
        k = turn(normed(_heads(_linear(x, kv_heads * hd, pre + "k_proj_w",
                                       cfg), kv_heads, hd), "k_norm_scale"))
        v = _heads(_linear(x, kv_heads * hd, pre + "v_proj_w", cfg),
                   kv_heads, hd)
    target = None
    if selection is not None:
        with name_scope("attn.attend.sparse"):
            ctx, target = layers.fused_attention(
                q, k, v, causal=True, scale=1.0 / math.sqrt(hd),
                select=selection, return_target=True)
    else:
        with name_scope("attn.attend.full" if window is None
                        else "attn.attend.window"):
            ctx = layers.fused_attention(q, k, v, causal=True,
                                         scale=1.0 / math.sqrt(hd),
                                         window=window)
    with name_scope("attn.proj"):
        ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                             [0, 0, heads * hd])
        if gate:
            ctx = layers.head_gate(
                ctx, _linear(x, heads * hd, pre + "g_proj_w", cfg))
        out = _linear(ctx, cfg.hidden_size, pre + "o_proj_w", cfg)
    return out if selection is None else (out, target)


def short_conv_heads(qkv, cfg, pre, heads, width):
    """A linear-attention layer's q, k, v from their projections `qkv`
    ([B, S, heads * width] each): a causal depthwise convolution of
    `short_conv_kernel_size` taps a channel without bias, then silu, cut
    into `heads` heads; q and k L2-normed a head, q times width^-0.5. Scope
    `kda.conv`."""
    with name_scope("kda.conv"):
        q, k, v = (layers.reshape(layers.causal_conv1d(
            t, cfg.short_conv_kernel_size,
            param_attr=_w(pre + f"{n}_conv_w", cfg), bias_attr=False,
            activation="silu"), [0, cfg.seq_len, heads, width])
            for n, t in zip("qkv", qkv))
        return layers.l2_norm(q, scale=width ** -0.5), layers.l2_norm(k), v


def embed_tokens(cfg):
    """(tokens [B, seq_len] int64, their embeddings [B, seq_len, hidden],
    the embedding parameter [vocab, hidden]), the lookup a gather of the
    rows held; scope `embed.tokens` (the gather, and in the backward its
    scatter-add)."""
    s, h = cfg.seq_len, cfg.hidden_size
    tokens = layers.data(name="tokens", shape=[s], dtype="int64")
    embed = layers.create_parameter([cfg.vocab_size, h], "float32",
                                    attr=_w("embed_tokens", cfg))
    with name_scope("embed.tokens"):
        return tokens, layers.reshape(
            layers.gather(embed, layers.reshape(tokens, [-1])),
            [-1, s, h]), embed


def next_token_loss(x, tokens, cfg, tied=None):
    """Final norm, the head over the vocabulary held, and the mean cross
    entropy of every position but a row's last against the token that
    follows it. All `seq_len` positions go through the head (the last one's
    label is the ignore index), so no shape in the step is `seq_len - 1`.
    Scopes `head.norm`, `head.untied` or `head.tied` (the matmul over the
    vocabulary), `head.loss` (the labels, the cross entropy, its mean).

    `tied`: a tied head, the embedding parameter [vocab, hidden] itself:
    the logits are `norm(x) E^T` under the scope `head.tied`, no `lm_head_w`
    is created, and the one parameter is read at two places of the Program,
    by `embed_tokens`' gather and by this matmul; its gradient is the sum of
    the gather's scatter-add and the matmul's (`framework/backward.py` adds
    up a variable's repeated gradients). None: an untied `lm_head_w`."""
    s = cfg.seq_len
    with name_scope("head.norm"):
        x = _norm(x, "final_norm_scale", cfg)
    if tied is None:
        with name_scope("head.untied"):
            logits = _linear(x, cfg.vocab_size, "lm_head_w", cfg)
    else:
        with name_scope("head.tied"):
            logits = layers.matmul(x, tied, transpose_y=True)
    with name_scope("head.loss"):
        nxt = layers.slice(tokens, [1], [1], [s])
        none = layers.fill_constant_batch_size_like(nxt, [-1, 1], "int64",
                                                    -100)
        labels = layers.unsqueeze(layers.concat([nxt, none], axis=1), [2])
        ce = layers.softmax_with_cross_entropy(logits, labels,
                                               ignore_index=-100)
        return layers.scale(layers.mean(ce), scale=s / (s - 1.0))


def build_causal_lm_program(cfg, model, decoder_layer, layer_indices,
                            auxiliary=None, tie_head=False):
    """Next-token objective over `tokens` [B, seq_len] (`next_token_loss`)
    of a decoder whose layer n is `decoder_layer(x, cfg, n)` -> (x_out,
    (top_idx, expert_load) or None) for n in `layer_indices`, under the
    span `program.build` with the family's name `model`.

    `auxiliary`: a list the layers append scalar losses of their own to
    (a learned indexer's, say) while they are built; the objective is then
    the next-token loss plus their sum, and the loss carries them, in
    order, as `_auxiliary_losses` and the next-token loss alone as
    `_lm_loss` (under recomputation that one lies inside the last segment
    and cannot be fetched: it is the loss less the others). None, or
    nothing appended: the next-token loss and today's Program.

    `tie_head`: the head reads the token embedding (`next_token_loss`'s
    `tied`); the gauge `lm.tied_head` says which the last Program built has.

    Returns (tokens, loss, routed): `routed` holds, per expert layer, the
    `(top_idx, expert_load)` variables a caller may fetch beside the loss
    (`expert_load` [experts held]: the assignments that fell on each). Layer
    boundaries land on the loss's `_layer_checkpoints`."""
    with RecordEvent("program.build", args={"model": model}):
        tokens, x, embed = embed_tokens(cfg)
        ckpts, routed = [], []
        for n in layer_indices:
            with name_scope("layer.residual"):
                x, r = decoder_layer(x, cfg, n)
            ckpts.append(x.name)
            if r is not None:
                routed.append(r)
        metrics.set_gauge("lm.tied_head", float(tie_head))
        loss = next_token_loss(x, tokens, cfg,
                               tied=embed if tie_head else None)
        if auxiliary:
            with name_scope("head.loss"):
                lm_loss, loss = loss, layers.sums([loss] + list(auxiliary))
            loss._lm_loss, loss._auxiliary_losses = lm_loss, list(auxiliary)
        loss._layer_checkpoints = ckpts
        return tokens, loss, routed


def sharding_rules(own) -> ShardingRules:
    """tp / ep rules as data: the model's `own` (its projections into heads
    and its feed-forward widths, column-parallel in and row-parallel out),
    then what every model here has: the attention's output projection
    row-parallel, the experts' leading dim over `ep`, the vocabulary over
    `tp`. A tied vocabulary is the embedding's rule alone: rows over `tp`
    serve the gather and, as the columns of E^T, the head; no parameter is
    named `lm_head_w` and that rule does not fire."""
    return moe_sharding_rules(extra=list(own) + [
        (r"_o_proj_w$", P("tp", None)),
        (r"^embed_tokens$", P("tp", None)),
        (r"^lm_head_w$", P(None, "tp")),
    ])


def record_expert_load(loads, tokens: int) -> dict:
    """The routing of one dispatch into the program's metrics, from the
    `expert_load` values fetched beside its losses (no dispatch and no
    sync of its own): `loads` [..., experts held], any leading axes (layer,
    step), `tokens` a step. Gauges `moe.local_assignments_per_token` (the
    (token, slot) choices that fell on a held expert, per token: top_k x
    held / total when routing is even) and `moe.load_max_over_mean` (the
    fullest held expert over the mean one, averaged over layers and
    steps); counter `moe.tokens_dropped`, which never rises: the op has
    no capacity. Returns the two gauges' values."""
    import numpy as np
    loads = np.asarray(loads, np.float64)
    per_tok = float(loads.sum(axis=-1).mean() / tokens)
    skew = float((loads.max(axis=-1)
                  / np.maximum(loads.mean(axis=-1), 1e-9)).mean())
    metrics.set_gauge("moe.local_assignments_per_token", per_tok)
    metrics.set_gauge("moe.load_max_over_mean", skew)
    metrics.inc("moe.tokens_dropped", 0)
    return {"local_assignments_per_token": per_tok,
            "load_max_over_mean": skew}
