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
from ..framework.program import name_scope
from ..parallel.mesh import ShardingRules
from . import causal_lm
from .causal_lm import (_heads, _linear, _norm, dense_ffn, gated_ffn,
                        record_expert_load)

__all__ = ["DeepseekV3Config", "build_causal_lm_program",
           "record_expert_load", "sharding_rules"]


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


def expert_layer(x, cfg: DeepseekV3Config, pre: str):
    """(this rank's routed part + the shared expert, top_idx,
    expert_load): sigmoid scores with a selection bias, gated experts,
    `n_shared_experts` shared ones as one of their summed width."""
    width = cfg.moe_intermediate_size * cfg.n_shared_experts
    return causal_lm.expert_layer(
        x, cfg, pre, experts_total=cfg.n_routed_experts,
        routed_scaling=cfg.routed_scaling_factor,
        shared=(gated_ffn, width) if width else None)


def decoder_layer(x, cfg: DeepseekV3Config, n: int):
    pre = f"l{n}_"
    x = layers.elementwise_add(
        x, latent_attention(_norm(x, pre + "attn_norm_scale", cfg), cfg, pre))
    f = _norm(x, pre + "ffn_norm_scale", cfg)
    if n < cfg.first_k_dense_replace:
        return layers.elementwise_add(
            x, dense_ffn(f, cfg.intermediate_size, pre + "mlp_", cfg)), None
    y, idx, load = expert_layer(f, cfg, pre)
    return layers.elementwise_add(x, y), (idx, load)


def build_causal_lm_program(cfg: DeepseekV3Config):
    """Next-token objective over `tokens` [B, seq_len]
    (`causal_lm.build_causal_lm_program`): (tokens, loss, routed), `routed`
    the `(top_idx, expert_load)` variables of each expert layer."""
    return causal_lm.build_causal_lm_program(
        cfg, "deepseek_v3", decoder_layer, range(cfg.num_hidden_layers))


def sharding_rules() -> ShardingRules:
    """tp / ep rules as data: column-parallel projections into heads and
    FFN widths, row-parallel projections out of them, the experts' leading
    dim over `ep`, the vocabulary over `tp`."""
    return causal_lm.sharding_rules([
        (r"_(q_proj|kv_b_proj)_w$", P(None, "tp")),
        (r"_(mlp|shared)_(gate|up)_w$", P(None, "tp")),
        (r"_(mlp|shared)_down_w$", P("tp", None)),
    ])
