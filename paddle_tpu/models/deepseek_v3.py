"""DeepSeek-V3-family causal LM (`model_type` deepseek_v3, `q_lora_rank`
null), static-graph builder: latent attention (MLA), leading dense
layers, then sparse expert layers with sigmoid routing, a selection bias,
no drops, and shared experts.

The configuration's keys are the published `config.json`'s. What one
expert-parallel rank holds is said beside them: `experts_held` experts
from `expert_offset` of the `n_routed_experts` the router scores. The
routed part of an expert layer is then this rank's share of the sum
(`layers.routed_moe`); on one chip there is no exchange, and nothing
stands in for the other ranks. A sliced vocabulary is a smaller
`vocab_size`.

Ops of the Program IR only. Layer boundaries land on the loss's
`_layer_checkpoints` (recompute, layer scan: the leading dense layer is
not isomorphic to the expert layers, so `apply_layer_scan` can roll the
expert layers alone). Device work carries `program.name_scope` names:
`mla.proj`, `mla.attend`, `moe.shared`; the routed op names its own
(`moe.route`, `moe.dispatch`, `moe.experts`, `moe.combine`).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

from jax.sharding import PartitionSpec as P

from .. import layers
from .. import initializer as I
from ..framework.program import name_scope
from ..layer_helper import ParamAttr
from ..observability.trace import RecordEvent
from ..parallel.mesh import ShardingRules, moe_sharding_rules


@dataclass
class DeepseekV3Config:
    vocab_size: int = 129280
    hidden_size: int = 7168
    num_hidden_layers: int = 61
    num_attention_heads: int = 128
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 3
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    seq_len: int = 4096
    # this rank's share of every expert layer (None: all the experts)
    experts_held: "int | None" = None
    expert_offset: int = 0

    @staticmethod
    def tiny():
        return DeepseekV3Config(
            vocab_size=256, hidden_size=64, num_hidden_layers=3,
            num_attention_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, kv_lora_rank=32, intermediate_size=128,
            moe_intermediate_size=32, n_routed_experts=8, n_shared_experts=2,
            num_experts_per_tok=2, first_k_dense_replace=1,
            routed_scaling_factor=2.448, seq_len=32)


def _w(name, cfg):
    return ParamAttr(name=name,
                     initializer=I.Normal(0.0, cfg.initializer_range))


def _linear(x, size, name, cfg):
    return layers.fc(x, size, num_flatten_dims=2, param_attr=_w(name, cfg),
                     bias_attr=False)


def _norm(x, name, cfg):
    return layers.rms_norm(x, epsilon=cfg.rms_norm_eps,
                           param_attr=ParamAttr(name=name))


def _heads(t, nh, width):
    return layers.transpose(layers.reshape(t, [0, 0, nh, width]),
                            [0, 2, 1, 3])                  # [B, nh, S, w]


def latent_attention(x, cfg: DeepseekV3Config, pre: str):
    """MLA without the query's low-rank step: K and V are expanded from a
    `kv_lora_rank` latent, one rotary key `k_pe` a token is shared by all
    heads; q and k are `qk_nope + qk_rope` wide, v and the output
    `v_head_dim`."""
    nh, nope, rope = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                      cfg.qk_rope_head_dim)
    vd, lora, s = cfg.v_head_dim, cfg.kv_lora_rank, cfg.seq_len
    with name_scope("mla.proj"):
        q = _heads(_linear(x, nh * (nope + rope), pre + "q_proj_w", cfg),
                   nh, nope + rope)
        q = layers.rotary_embedding(q, theta=cfg.rope_theta, rotary_dim=rope)
        kva = _linear(x, lora + rope, pre + "kv_a_proj_w", cfg)
        c, k_pe = layers.split(kva, [lora, rope], dim=2)
        kv = _heads(_linear(_norm(c, pre + "kv_a_norm_scale", cfg),
                            nh * (nope + vd), pre + "kv_b_proj_w", cfg),
                    nh, nope + vd)
        k_nope, v = layers.split(kv, [nope, vd], dim=3)
        k_pe = layers.rotary_embedding(
            layers.reshape(k_pe, [0, 1, s, rope]), theta=cfg.rope_theta)
        k = layers.concat([k_nope, layers.expand(k_pe, [1, nh, 1, 1])],
                          axis=3)
    with name_scope("mla.attend"):
        ctx = layers.fused_attention(
            q, k, v, causal=True, scale=1.0 / math.sqrt(nope + rope))
    with name_scope("mla.proj"):
        ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                             [0, 0, nh * vd])
        return _linear(ctx, cfg.hidden_size, pre + "o_proj_w", cfg)


def gated_ffn(x, width, pre, cfg):
    """W_down(silu(W_gate x) * W_up x)."""
    return _linear(
        layers.swiglu(_linear(x, width, pre + "gate_w", cfg),
                      _linear(x, width, pre + "up_w", cfg)),
        cfg.hidden_size, pre + "down_w", cfg)


def expert_layer(x, cfg: DeepseekV3Config, pre: str):
    """(this rank's routed part + the shared expert, top_idx,
    expert_load)."""
    h, f = cfg.hidden_size, cfg.moe_intermediate_size
    held = cfg.experts_held or cfg.n_routed_experts
    gate_w = layers.create_parameter(
        [h, cfg.n_routed_experts], "float32", attr=_w(pre + "router_w", cfg))
    bias = layers.create_parameter(
        [cfg.n_routed_experts], "float32",
        attr=ParamAttr(name=pre + "router_bias", trainable=False,
                       initializer=I.Constant(0.0)))
    experts = [layers.create_parameter(
        shape, "float32", attr=_w(pre + f"experts_{n}_w", cfg))
        for n, shape in (("gate", [held, h, f]), ("up", [held, h, f]),
                         ("down", [held, f, h]))]
    routed, idx, load = layers.routed_moe(
        x, gate_w, *experts, top_k=cfg.num_experts_per_tok, select_bias=bias,
        routed_scaling=cfg.routed_scaling_factor,
        norm_topk=cfg.norm_topk_prob, experts_total=cfg.n_routed_experts,
        expert_offset=cfg.expert_offset)
    if not cfg.n_shared_experts:
        return routed, idx, load
    with name_scope("moe.shared"):
        shared = gated_ffn(x, f * cfg.n_shared_experts, pre + "shared_", cfg)
        return layers.elementwise_add(routed, shared), idx, load


def decoder_layer(x, cfg: DeepseekV3Config, n: int):
    pre = f"l{n}_"
    x = layers.elementwise_add(
        x, latent_attention(_norm(x, pre + "attn_norm_scale", cfg), cfg, pre))
    f = _norm(x, pre + "ffn_norm_scale", cfg)
    if n < cfg.first_k_dense_replace:
        return layers.elementwise_add(
            x, gated_ffn(f, cfg.intermediate_size, pre + "mlp_", cfg)), None
    y, idx, load = expert_layer(f, cfg, pre)
    return layers.elementwise_add(x, y), (idx, load)


def embed_tokens(cfg):
    """(tokens [B, seq_len] int64, their embeddings [B, seq_len, hidden]),
    the lookup a gather of the rows held."""
    s, h = cfg.seq_len, cfg.hidden_size
    tokens = layers.data(name="tokens", shape=[s], dtype="int64")
    embed = layers.create_parameter([cfg.vocab_size, h], "float32",
                                    attr=_w("embed_tokens", cfg))
    return tokens, layers.reshape(
        layers.gather(embed, layers.reshape(tokens, [-1])), [-1, s, h])


def next_token_loss(x, tokens, cfg):
    """Final norm, untied head over the vocabulary held, and the mean cross
    entropy of every position but a row's last against the token that
    follows it. All `seq_len` positions go through the head (the last one's
    label is the ignore index), so no shape in the step is `seq_len - 1`."""
    s = cfg.seq_len
    x = _norm(x, "final_norm_scale", cfg)
    logits = _linear(x, cfg.vocab_size, "lm_head_w", cfg)
    nxt = layers.slice(tokens, [1], [1], [s])
    none = layers.fill_constant_batch_size_like(nxt, [-1, 1], "int64", -100)
    labels = layers.unsqueeze(layers.concat([nxt, none], axis=1), [2])
    ce = layers.softmax_with_cross_entropy(logits, labels, ignore_index=-100)
    return layers.scale(layers.mean(ce), scale=s / (s - 1.0))


def build_causal_lm_program(cfg: DeepseekV3Config):
    """Next-token objective over `tokens` [B, seq_len] (`next_token_loss`).

    Returns (tokens, loss, routed): `routed` holds, per expert layer, the
    `(top_idx, expert_load)` variables a caller may fetch beside the loss
    (`expert_load` [experts held]: the assignments that fell on each)."""
    with RecordEvent("program.build", args={"model": "deepseek_v3"}):
        tokens, x = embed_tokens(cfg)
        ckpts, routed = [], []
        for n in range(cfg.num_hidden_layers):
            x, r = decoder_layer(x, cfg, n)
            ckpts.append(x.name)
            if r is not None:
                routed.append(r)
        loss = next_token_loss(x, tokens, cfg)
        loss._layer_checkpoints = ckpts
        return tokens, loss, routed


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
    from ..observability import metrics
    loads = np.asarray(loads, np.float64)
    per_tok = float(loads.sum(axis=-1).mean() / tokens)
    skew = float((loads.max(axis=-1)
                  / np.maximum(loads.mean(axis=-1), 1e-9)).mean())
    metrics.set_gauge("moe.local_assignments_per_token", per_tok)
    metrics.set_gauge("moe.load_max_over_mean", skew)
    metrics.inc("moe.tokens_dropped", 0)
    return {"local_assignments_per_token": per_tok,
            "load_max_over_mean": skew}


def sharding_rules() -> ShardingRules:
    """tp / ep rules as data: column-parallel projections into heads and
    FFN widths, row-parallel projections out of them, the experts' leading
    dim over `ep`, the vocabulary over `tp`."""
    return moe_sharding_rules(extra=[
        (r"_(q_proj|kv_b_proj)_w$", P(None, "tp")),
        (r"_o_proj_w$", P("tp", None)),
        (r"_(mlp|shared)_(gate|up)_w$", P(None, "tp")),
        (r"_(mlp|shared)_down_w$", P("tp", None)),
        (r"^embed_tokens$", P("tp", None)),
        (r"^lm_head_w$", P(None, "tp")),
    ])
