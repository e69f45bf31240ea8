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

from dataclasses import dataclass, field

from jax.sharding import PartitionSpec as P

from .. import layers
from ..parallel.mesh import ShardingRules
from . import causal_lm
from .causal_lm import _norm, record_expert_load

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
    return causal_lm.grouped_attention(
        x, cfg, pre, cfg.num_attention_heads, cfg.num_key_value_heads,
        rotary=lambda t: _rotary(t, cfg, kind),
        window=cfg.sliding_window if kind == SLIDING else None)


def expert_layer(x, cfg: MellumConfig, pre: str):
    """(this rank's routed part, top_idx, expert_load): a softmax over ALL
    `num_experts` scores, its top `num_experts_per_tok`, their weights
    divided by their sum; no bias, no shared expert."""
    return causal_lm.expert_layer(
        x, cfg, pre, experts_total=cfg.num_experts, scoring="softmax",
        select_bias=False)


def decoder_layer(x, cfg: MellumConfig, n: int):
    pre = f"l{n}_"
    x = layers.elementwise_add(x, grouped_attention(
        _norm(x, pre + "attn_norm_scale", cfg), cfg, pre, cfg.kind(n)))
    y, idx, load = expert_layer(_norm(x, pre + "ffn_norm_scale", cfg), cfg,
                                pre)
    return layers.elementwise_add(x, y), (idx, load)


def build_causal_lm_program(cfg: MellumConfig):
    """Next-token objective over `tokens` [B, seq_len]
    (`causal_lm.build_causal_lm_program`): (tokens, loss, routed), `routed`
    the `(top_idx, expert_load)` variables of each layer."""
    return causal_lm.build_causal_lm_program(
        cfg, "mellum", decoder_layer, range(cfg.num_hidden_layers))


def sharding_rules() -> ShardingRules:
    """tp / ep rules as data: q, k, v column-parallel by head, the output
    projection row-parallel, the experts' leading dim over `ep`, the
    vocabulary over `tp`. k and v split by KV head: `tp` may not pass
    `num_key_value_heads` (4 as published)."""
    return causal_lm.sharding_rules([(r"_(q|k|v)_proj_w$", P(None, "tp"))])
