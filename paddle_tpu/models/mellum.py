"""Mellum-2-family causal LM (`model_type` mellum), static-graph builder: a
decoder whose layers differ by kind inside a period (`layer_types`:
sliding-window and full attention, 3 : 1 as published), 32 query heads on
4 KV heads, rotary positions on half-split pairs whose frequency rule is
the kind's (`rope_parameters`: default on the sliding layers, yarn with its
cos / sin scale on the full ones), and in every layer softmax-routed
experts, top-8 of 64, weights normalised, no bias, no shared expert, no
dense layer.

The configuration's keys are the published `config.json`'s. What one
expert-parallel rank holds is said beside them, as in
`models/deepseek_v3.py`: `experts_held` experts from `expert_offset` of the
`num_experts` the router scores; a sliced vocabulary is a smaller
`vocab_size`. On one chip the routed part is this rank's share of the sum
and nothing stands in for the other ranks.

Ops of the Program IR only, unrolled: the layers of a period are not
isomorphic (window attr, rotary attrs), so `apply_layer_scan` has whole
periods to roll, not layers. Layer boundaries land on the loss's
`_layer_checkpoints`. Device work carries `program.name_scope` names:
`attn.proj`, `attn.attend.window`, `attn.attend.full`; the routed op names
its own (`moe.route`, `moe.dispatch`, `moe.experts`, `moe.combine`).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

from jax.sharding import PartitionSpec as P

from .. import layers
from ..framework.program import name_scope
from ..observability.trace import RecordEvent
from ..parallel.mesh import ShardingRules, moe_sharding_rules
from .deepseek_v3 import (_heads, _linear, _norm, _w, embed_tokens,
                          next_token_loss, record_expert_load)

__all__ = ["MellumConfig", "build_causal_lm_program", "record_expert_load",
           "sharding_rules"]

SLIDING, FULL = "sliding_attention", "full_attention"


def _published_rope():
    return {
        FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
               "original_max_position_embeddings": 8192, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 1.2772588722239782},
        SLIDING: {"rope_type": "default", "rope_theta": 500000}}


@dataclass
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 896
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    sliding_window: int = 1024
    # kind of layer n; shorter than the depth, it repeats (the period)
    layer_types: tuple = (SLIDING, SLIDING, SLIDING, FULL)
    rope_parameters: dict = field(default_factory=_published_rope)
    initializer_range: float = 0.02
    seq_len: int = 8192
    # this rank's share of every expert layer (None: all the experts)
    experts_held: "int | None" = None
    expert_offset: int = 0

    def kind(self, n: int) -> str:
        return self.layer_types[n % len(self.layer_types)]

    @staticmethod
    def tiny():
        return MellumConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
            sliding_window=8, seq_len=32)


def _rotary(t, cfg: MellumConfig, kind: str):
    """Rotary positions on all `head_dim` features, half-split pairs
    (j, j + head_dim / 2), by the frequency rule of the layer's kind."""
    rope = cfg.rope_parameters[kind]
    return layers.rotary_embedding(
        t, theta=rope["rope_theta"], layout="half",
        rope_type=rope["rope_type"], factor=rope.get("factor", 1.0),
        original_max_position=rope.get("original_max_position_embeddings", 0),
        beta_fast=rope.get("beta_fast", 32.0),
        beta_slow=rope.get("beta_slow", 1.0),
        scale=rope.get("attention_factor", 1.0))


def grouped_attention(x, cfg: MellumConfig, pre: str, kind: str):
    """`num_attention_heads` query heads on `num_key_value_heads` KV heads
    (query head h attends KV head h // group), causal, and in a sliding
    layer over the last `sliding_window` keys only. K and V go to the
    attention op at their own head count."""
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    with name_scope("attn.proj"):
        q = _rotary(_heads(_linear(x, nh * hd, pre + "q_proj_w", cfg),
                           nh, hd), cfg, kind)
        k = _rotary(_heads(_linear(x, nkv * hd, pre + "k_proj_w", cfg),
                           nkv, hd), cfg, kind)
        v = _heads(_linear(x, nkv * hd, pre + "v_proj_w", cfg), nkv, hd)
    sliding = kind == SLIDING
    with name_scope("attn.attend.window" if sliding else "attn.attend.full"):
        ctx = layers.fused_attention(
            q, k, v, causal=True, scale=1.0 / math.sqrt(hd),
            window=cfg.sliding_window if sliding else None)
    with name_scope("attn.proj"):
        ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                             [0, 0, nh * hd])
        return _linear(ctx, cfg.hidden_size, pre + "o_proj_w", cfg)


def expert_layer(x, cfg: MellumConfig, pre: str):
    """(this rank's routed part, top_idx, expert_load): a softmax over ALL
    `num_experts` scores, its top `num_experts_per_tok`, their weights
    divided by their sum."""
    h, f = cfg.hidden_size, cfg.moe_intermediate_size
    held = cfg.experts_held or cfg.num_experts
    gate_w = layers.create_parameter(
        [h, cfg.num_experts], "float32", attr=_w(pre + "router_w", cfg))
    experts = [layers.create_parameter(
        shape, "float32", attr=_w(pre + f"experts_{n}_w", cfg))
        for n, shape in (("gate", [held, h, f]), ("up", [held, h, f]),
                         ("down", [held, f, h]))]
    return layers.routed_moe(
        x, gate_w, *experts, top_k=cfg.num_experts_per_tok,
        scoring="softmax", norm_topk=cfg.norm_topk_prob,
        experts_total=cfg.num_experts, expert_offset=cfg.expert_offset)


def decoder_layer(x, cfg: MellumConfig, n: int):
    pre = f"l{n}_"
    x = layers.elementwise_add(x, grouped_attention(
        _norm(x, pre + "attn_norm_scale", cfg), cfg, pre, cfg.kind(n)))
    y, idx, load = expert_layer(_norm(x, pre + "ffn_norm_scale", cfg), cfg,
                                pre)
    return layers.elementwise_add(x, y), (idx, load)


def build_causal_lm_program(cfg: MellumConfig):
    """Next-token objective over `tokens` [B, seq_len]
    (`models.deepseek_v3.next_token_loss`). Returns (tokens, loss, routed):
    `routed` holds, per layer, the `(top_idx, expert_load)` variables a
    caller may fetch beside the loss."""
    with RecordEvent("program.build", args={"model": "mellum"}):
        tokens, x = embed_tokens(cfg)
        ckpts, routed = [], []
        for n in range(cfg.num_hidden_layers):
            x, r = decoder_layer(x, cfg, n)
            ckpts.append(x.name)
            routed.append(r)
        loss = next_token_loss(x, tokens, cfg)
        loss._layer_checkpoints = ckpts
        return tokens, loss, routed


def sharding_rules() -> ShardingRules:
    """tp / ep rules as data: q, k, v column-parallel by head, the output
    projection row-parallel, the experts' leading dim over `ep`, the
    vocabulary over `tp`. k and v split by KV head: `tp` may not pass
    `num_key_value_heads` (4 as published)."""
    return moe_sharding_rules(extra=[
        (r"_(q|k|v)_proj_w$", P(None, "tp")),
        (r"_o_proj_w$", P("tp", None)),
        (r"^embed_tokens$", P("tp", None)),
        (r"^lm_head_w$", P(None, "tp")),
    ])
