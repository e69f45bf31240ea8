"""Laguna-family causal LM (`model_type` laguna), static-graph builder: a
decoder whose layers differ by kind (`layer_types`: sliding-window and full
attention, 3 : 1 as published, the full layer FIRST in a period) and whose
query-head COUNT differs with the kind (`num_attention_heads_per_layer`: 64
in a sliding layer, 48 in a full one, both on 8 KV heads: groups of 8 and of
6). Rotary positions by the kind's `rope_parameters`: a sliding layer turns
all `head_dim` features by the default rule; a full layer turns only the
FIRST `partial_rotary_factor` x `head_dim` of a head (half-split pairs
(j, j + 32) inside the turned 64, yarn's table over 64, its
`attention_factor` on cos and sin) and passes the rest. The heads' outputs
pass an element-wise sigmoid gate from the layer's normed input (`gating`).
Layer 0 feeds forward densely (`mlp_layer_types`), every other layer through
sigmoid-routed experts, top `num_experts_per_tok` of `num_experts`, weights
over their sum times `moe_routed_scaling_factor`, beside one shared expert.

The configuration's keys are the published `config.json`'s. What one
expert-parallel rank holds is said beside them, as in `models/mellum.py` and
`models/lfm2.py`: `experts_held` experts from `expert_offset` of the
`num_experts` the router scores; layers `first_layer` .. + `num_layers_held`
(a layer's kind, head count and feed-forward part follow its published
index); a sliced vocabulary is a smaller `vocab_size`. On one chip the
routed part is this rank's share of the sum and nothing stands in for the
other ranks.

Ops of the Program IR only, unrolled. Layer boundaries land on the loss's
`_layer_checkpoints`. Device work carries `program.name_scope` names:
`attn.proj` (rotary and the gate inside it), `attn.attend.window`,
`attn.attend.full` (which here stand for different head counts), `ffn.dense`,
`moe.shared`; the routed op names its own (`moe.*`).
"""
from __future__ import annotations

from dataclasses import dataclass, field

from jax.sharding import PartitionSpec as P

from .. import layers
from ..parallel.mesh import ShardingRules
from . import causal_lm
from .causal_lm import _norm, dense_ffn, gated_ffn, record_expert_load

__all__ = ["LagunaConfig", "build_causal_lm_program", "record_expert_load",
           "sharding_rules"]

SLIDING, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"


def _published_rope():
    return {
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
               "original_max_position_embeddings": 4096, "beta_slow": 1,
               "beta_fast": 64, "attention_factor": 1.4158883083359672,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1}}


@dataclass
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    # one entry a published layer, all three
    layer_types: tuple = (FULL, SLIDING, SLIDING, SLIDING) * 10
    num_attention_heads_per_layer: tuple = (48, 64, 64, 64) * 10
    mlp_layer_types: tuple = (DENSE,) + (SPARSE,) * 39
    sliding_window: int = 512
    rope_parameters: dict = field(default_factory=_published_rope)
    gating: bool = True
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
    moe_apply_router_weight_on_input: bool = False
    # in no published key: the chosen scores are divided by their sum
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    seq_len: int = 8192
    # this rank's share (None: everything)
    experts_held: "int | None" = None
    expert_offset: int = 0
    first_layer: int = 0
    num_layers_held: "int | None" = None

    def __post_init__(self):
        for key in ("layer_types", "num_attention_heads_per_layer",
                    "mlp_layer_types"):
            if len(getattr(self, key)) != self.num_hidden_layers:
                raise ValueError(
                    f"{key} has {len(getattr(self, key))} entries for "
                    f"num_hidden_layers = {self.num_hidden_layers}")
        if self.moe_apply_router_weight_on_input:
            raise ValueError(
                "moe_apply_router_weight_on_input = True: the builder's "
                "experts weigh their OUTPUT, the published False, only")

    def layers_here(self) -> range:
        held = (self.num_hidden_layers - self.first_layer
                if self.num_layers_held is None else self.num_layers_held)
        return range(self.first_layer, self.first_layer + held)

    @staticmethod
    def tiny():
        """Published layers 0..4 of 8 (full + dense, three sliding, full):
        groups of 6 and of 8 on two KV heads, a partial and a whole rotary,
        a window shorter than the row, half of the experts."""
        rope = _published_rope()
        rope[FULL].update(original_max_position_embeddings=16, factor=4)
        return LagunaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=8, num_key_value_heads=2, head_dim=16,
            layer_types=(FULL, SLIDING, SLIDING, SLIDING) * 2,
            num_attention_heads_per_layer=(12, 16, 16, 16) * 2,
            mlp_layer_types=(DENSE,) + (SPARSE,) * 7, sliding_window=8,
            rope_parameters=rope, num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            seq_len=32, experts_held=4, expert_offset=4, num_layers_held=5)


def _rotary(t, cfg: LagunaConfig, kind: str):
    """Rotary positions by the rule of the layer's kind, half-split pairs:
    the FIRST `partial_rotary_factor` x `head_dim` features of a head turn
    (pairs (j, j + half) inside them, the frequency table over that many),
    the rest passes; a factor of 1 turns the whole head."""
    rope = cfg.rope_parameters[kind]
    turned = int(cfg.head_dim * rope.get("partial_rotary_factor", 1))
    return layers.rotary_embedding(
        t, theta=rope["rope_theta"], layout="half", rotary_dim=turned,
        rotary_start=0 if turned < cfg.head_dim else None,
        rope_type=rope["rope_type"], factor=rope.get("factor", 1.0),
        original_max_position=rope.get("original_max_position_embeddings", 0),
        beta_fast=rope.get("beta_fast", 32.0),
        beta_slow=rope.get("beta_slow", 1.0),
        scale=rope.get("attention_factor", 1.0))


def gated_grouped_attention(x, cfg: LagunaConfig, pre: str, n: int):
    """Published layer n's `num_attention_heads_per_layer[n]` query heads on
    the `num_key_value_heads` KV heads (query head h attends KV head
    h // group), causal, in a sliding layer over the last `sliding_window`
    keys only, the heads' outputs under an element-wise sigmoid gate from
    the layer's input (`gating`)."""
    kind = cfg.layer_types[n]
    return causal_lm.grouped_attention(
        x, cfg, pre, cfg.num_attention_heads_per_layer[n],
        cfg.num_key_value_heads, rotary=lambda t: _rotary(t, cfg, kind),
        window=cfg.sliding_window if kind == SLIDING else None,
        gate=bool(cfg.gating))


def expert_layer(x, cfg: LagunaConfig, pre: str):
    """(this rank's routed part + the shared expert, top_idx, expert_load):
    sigmoid scores over ALL `num_experts`, the top `num_experts_per_tok` of
    score + bias (a buffer, 0 and moved by no step), no groups, their
    weights divided by their sum and times `moe_routed_scaling_factor`;
    gated experts of `moe_intermediate_size`, the shared one of
    `shared_expert_intermediate_size`, unscaled and ungated."""
    return causal_lm.expert_layer(
        x, cfg, pre, experts_total=cfg.num_experts,
        routed_scaling=cfg.moe_routed_scaling_factor,
        shared=(gated_ffn, cfg.shared_expert_intermediate_size))


def decoder_layer(x, cfg: LagunaConfig, n: int):
    """Published layer n: (x_out, (top_idx, expert_load) or None)."""
    pre = f"l{n}_"
    x = layers.elementwise_add(x, gated_grouped_attention(
        _norm(x, pre + "attn_norm_scale", cfg), cfg, pre, n))
    f = _norm(x, pre + "ffn_norm_scale", cfg)
    if cfg.mlp_layer_types[n] == DENSE:
        return layers.elementwise_add(
            x, dense_ffn(f, cfg.intermediate_size, pre + "mlp_", cfg)), None
    y, idx, load = expert_layer(f, cfg, pre)
    return layers.elementwise_add(x, y), (idx, load)


def build_causal_lm_program(cfg: LagunaConfig):
    """Next-token objective over `tokens` [B, seq_len]
    (`causal_lm.build_causal_lm_program`) of the layers held: (tokens,
    loss, routed), `routed` the `(top_idx, expert_load)` variables of each
    expert layer."""
    return causal_lm.build_causal_lm_program(
        cfg, "laguna", decoder_layer, cfg.layers_here())


def sharding_rules() -> ShardingRules:
    """tp / ep rules as data: q, k, v and the gate column-parallel by head
    and the output projection row-parallel, the dense part and the shared
    expert by their widths, the experts' leading dim over `ep`, the
    vocabulary over `tp`. k and v split by KV head and a layer's query
    heads by whole groups: `tp` may not pass `num_key_value_heads` (8 as
    published: 6 or 8 query heads a chip by the layer's kind)."""
    return causal_lm.sharding_rules([
        (r"_(q|k|v|g)_proj_w$", P(None, "tp")),
        (r"_(mlp|shared)_(gate|up)_w$", P(None, "tp")),
        (r"_(mlp|shared)_down_w$", P("tp", None)),
    ])
