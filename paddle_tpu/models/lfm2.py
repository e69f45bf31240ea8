"""LFM2-family causal LM with sparse experts (`model_type` lfm2_moe),
static-graph builder: a decoder most of whose layers mix along the sequence
by a GATED SHORT CONVOLUTION and no attention (`layer_types`: "conv" and
"full_attention", 3 : 1 as published), attention with 32 query heads on 8
KV heads whose q and k are RMS-normed a head before the rotary turn,
leading dense layers (`num_dense_layers`), then in every layer sigmoid-
routed experts with a selection bias, top-4 of 64, weights over their sum,
no shared expert; and a head TIED to the token embedding.

The short-convolution mixer: one projection of the normed input into three
streams `[B | C | u]`, `y = C * conv(B * u)` with `conv` a depthwise causal
convolution of `conv_L_cache` taps a channel (no bias, no activation; the
gates are plain products), one projection out: `layers.gated_short_conv`
between two `fc`. Its state in a decode service would be the last
`conv_L_cache - 1` rows of `B * u`, whatever the context's length.

The configuration's keys are the published `config.json`'s. What one
expert-parallel rank holds is said beside them, as in `models/ling.py`:
`experts_held` experts from `expert_offset` of the `num_experts` the router
scores, published layers `first_layer` .. + `num_layers_held` (a layer's
mixer and feed-forward part follow its published index), a sliced
vocabulary as a smaller `vocab_size`. On one chip the routed part is this
rank's share of the sum and nothing stands in for the other ranks.

Ops of the Program IR only, unrolled. Layer boundaries land on the loss's
`_layer_checkpoints`. Device work carries scopes: `conv.in_proj`,
`conv.mix`, `conv.out_proj`; `attn.proj`, `attn.qk_norm`,
`attn.attend.full`; `head.tied`; the routed op names its own (`moe.*`).
"""
from __future__ import annotations

from dataclasses import dataclass

from jax.sharding import PartitionSpec as P

from .. import layers
from ..framework.program import name_scope
from ..parallel.mesh import ShardingRules
from . import causal_lm
from .causal_lm import _linear, _norm, _w, dense_ffn, record_expert_load

__all__ = ["Lfm2Config", "build_causal_lm_program", "record_expert_load",
           "sharding_rules"]

CONV, FULL = "conv", "full_attention"
# what the published code adds to the sum `norm_topk_prob` divides by
NORM_TOPK_EPS = 1e-6


@dataclass
class Lfm2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    # kind of layer n; shorter than the depth, it repeats (the period)
    layer_types: tuple = (CONV, CONV, FULL, CONV)
    conv_L_cache: int = 3
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    num_dense_layers: int = 2
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    norm_eps: float = 1e-5
    initializer_range: float = 0.02
    seq_len: int = 8192
    # this chip's share (None: everything)
    experts_held: "int | None" = None
    expert_offset: int = 0
    first_layer: int = 0
    num_layers_held: "int | None" = None

    def kind(self, n: int) -> str:
        return self.layer_types[n % len(self.layer_types)]

    def layers_here(self) -> range:
        held = (self.num_hidden_layers - self.first_layer
                if self.num_layers_held is None else self.num_layers_held)
        return range(self.first_layer, self.first_layer + held)

    @staticmethod
    def tiny(num_dense_layers: int = 1):
        """Both kinds of mixer, dense and expert layers in one period; a
        row that is no multiple of 4."""
        return Lfm2Config(
            vocab_size=256, hidden_size=64, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            num_dense_layers=num_dense_layers, intermediate_size=128,
            moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
            seq_len=30)


def short_conv_mixer(x, cfg: Lfm2Config, pre: str):
    """W_out (C * conv(B * u)), [B | C | u] = x W_in: no bias, no
    activation, `conv_L_cache` taps a channel."""
    with name_scope("conv.in_proj"):
        bcx = _linear(x, 3 * cfg.hidden_size, pre + "conv_in_proj_w", cfg)
    with name_scope("conv.mix"):
        y = layers.gated_short_conv(bcx, cfg.conv_L_cache,
                                    param_attr=_w(pre + "conv_w", cfg))
    with name_scope("conv.out_proj"):
        return _linear(y, cfg.hidden_size, pre + "conv_out_proj_w", cfg)


def grouped_attention(x, cfg: Lfm2Config, pre: str):
    """`num_attention_heads` query heads on `num_key_value_heads` KV heads,
    q and k normed a head, then rotary positions on all `head_dim` features
    (half-split pairs, the default rule), causal over the whole row."""
    return causal_lm.grouped_attention(
        x, cfg, pre, cfg.num_attention_heads, cfg.num_key_value_heads,
        rotary=lambda t: layers.rotary_embedding(t, theta=cfg.rope_theta,
                                                 layout="half"),
        qk_norm=True)


def expert_layer(x, cfg: Lfm2Config, pre: str):
    """(this rank's routed part, top_idx, expert_load): sigmoid scores over
    ALL `num_experts`, the top `num_experts_per_tok` of score + bias
    (`use_expert_bias`), their weights divided by their sum + 1e-6 and
    times `routed_scaling_factor`; no shared expert."""
    return causal_lm.expert_layer(
        x, cfg, pre, experts_total=cfg.num_experts,
        select_bias=cfg.use_expert_bias,
        routed_scaling=cfg.routed_scaling_factor,
        norm_topk_eps=NORM_TOPK_EPS)


def decoder_layer(x, cfg: Lfm2Config, n: int):
    """Published layer n: (x_out, (top_idx, expert_load) or None)."""
    pre = f"l{n}_"
    mixer = short_conv_mixer if cfg.kind(n) == CONV else grouped_attention
    x = layers.elementwise_add(
        x, mixer(_norm(x, pre + "operator_norm_scale", cfg), cfg, pre))
    f = _norm(x, pre + "ffn_norm_scale", cfg)
    if n < cfg.num_dense_layers:
        return layers.elementwise_add(
            x, dense_ffn(f, cfg.intermediate_size, pre + "mlp_", cfg)), None
    y, idx, load = expert_layer(f, cfg, pre)
    return layers.elementwise_add(x, y), (idx, load)


def build_causal_lm_program(cfg: Lfm2Config):
    """Next-token objective over `tokens` [B, seq_len]
    (`causal_lm.build_causal_lm_program`) of the layers held, the head tied
    to the embedding: (tokens, loss, routed), `routed` the `(top_idx,
    expert_load)` variables of each expert layer."""
    return causal_lm.build_causal_lm_program(
        cfg, "lfm2", decoder_layer, cfg.layers_here(), tie_head=True)


def sharding_rules() -> ShardingRules:
    """tp / ep rules as data: q, k, v column-parallel by head and the
    output projection row-parallel, the dense part by its width, the
    experts' leading dim over `ep`, the tied vocabulary's rows over `tp`.
    The short-convolution mixer stays whole on every chip, like the
    per-head norm scales: `conv_in_proj_w`'s columns are [B | C | u] side
    by side, and a split by channel would have to cut each third."""
    return causal_lm.sharding_rules([
        (r"_(q|k|v)_proj_w$", P(None, "tp")),
        (r"_mlp_(gate|up)_w$", P(None, "tp")),
        (r"_mlp_down_w$", P("tp", None)),
    ])
