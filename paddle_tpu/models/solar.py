"""Solar-Open2-family hybrid causal LM (`model_type` solar_open2),
static-graph builder: a decoder whose layers listed in `gqa_layers` (every
fourth as published) attend by softmax on grouped KV heads WITHOUT rotary
positions under an element-wise sigmoid output gate (`use_gqa_gate`), and
whose other layers are Kimi-delta linear attention in its original form:
three short causal convs, an L2 norm on q and k, a per-channel decay with NO
lower bound (`g = -exp(A_log) softplus(.)`, its projection low-rank:
`kda_use_full_proj` false), `beta = 2 sigmoid(.)` so that `I - beta k k^T`
may have negative eigenvalues (`kda_allow_neg_eigval`), the gated delta rule
in its chunked form, a per-head RMS norm under an element-wise low-rank
sigmoid gate. Every layer's feed-forward part is sparse
(`first_k_dense_replace` 0): sigmoid-routed experts, top `num_experts_per_
tok` of `n_routed_experts`, weights over their sum, beside one shared
expert.

The configuration's keys are the published `config.json`'s
(`linear_attn_config`'s `num_heads` and `head_dim` as `linear_num_heads` and
`linear_head_dim`). What one chip of a deployment holds is said beside
them, as in `models/ling.py`: `experts_held` experts from `expert_offset`
of the `n_routed_experts` the router scores; `heads_held` of the
`num_attention_heads` query heads on `kv_heads_held` of the
`num_key_value_heads`, and `linear_heads_held` of the `linear_num_heads`
(the projections into heads are built for the held heads only, and the
output projection's rows with them); layers `first_layer` .. +
`num_layers_held` (a layer's kind follows its published index); a sliced
vocabulary is a smaller `vocab_size`. On one chip the routed part and the
attention output are this chip's share of their sums and nothing stands in
for the others.

Ops of the Program IR only, unrolled. Layer boundaries land on the loss's
`_layer_checkpoints`. Device work carries `program.name_scope` names:
`kda.proj` (both low-rank pairs with it), `kda.conv`, `kda.gate`,
`kda.scan`, `kda.out`; `attn.proj` (the output gate with it),
`attn.attend.full`; `moe.shared`; the routed op names its own (`moe.*`).
"""
from __future__ import annotations

from dataclasses import dataclass

from jax.sharding import PartitionSpec as P

from .. import layers
from .. import initializer as I
from ..framework.program import name_scope
from ..layer_helper import ParamAttr
from ..parallel.mesh import ShardingRules
from . import causal_lm
from .causal_lm import (_linear, _norm, _w, gated_ffn, record_expert_load,
                        short_conv_heads)

__all__ = ["SolarConfig", "build_causal_lm_program", "record_expert_load",
           "sharding_rules"]

KDA, GQA = "kda", "gqa"


@dataclass
class SolarConfig:
    vocab_size: int = 196608
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    # the layers that attend by softmax; every other one is linear attention
    gqa_layers: tuple = tuple(range(0, 48, 4))
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    use_rope: bool = False
    use_gqa_gate: bool = True
    linear_num_heads: int = 64
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    kda_chunk_size: int = 64
    first_k_dense_replace: int = 0
    intermediate_size: int = 10240      # read by no layer: none is dense
    moe_intermediate_size: int = 1280
    n_routed_experts: int = 320
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    seq_len: int = 4096
    # this chip's share (None: everything)
    experts_held: "int | None" = None
    expert_offset: int = 0
    heads_held: "int | None" = None
    kv_heads_held: "int | None" = None
    linear_heads_held: "int | None" = None
    first_layer: int = 0
    num_layers_held: "int | None" = None

    def __post_init__(self):
        for key, built in (("use_rope", False), ("kda_use_full_proj", False),
                           ("first_k_dense_replace", 0),
                           ("n_shared_experts", 1)):
            if getattr(self, key) != built:
                raise ValueError(
                    f"{key} = {getattr(self, key)}: the builder has the "
                    f"published {built} only")

    def kind(self, n: int) -> str:
        return GQA if n in self.gqa_layers else KDA

    def layers_here(self) -> range:
        held = (self.num_hidden_layers - self.first_layer
                if self.num_layers_held is None else self.num_layers_held)
        return range(self.first_layer, self.first_layer + held)

    def held(self, share: str, total: str) -> int:
        """The count under `share`, or all `total` of them."""
        count, of = getattr(self, share) or getattr(self, total), getattr(
            self, total)
        if count > of:
            raise ValueError(f"{share} = {count} of {total} = {of}")
        return count

    @staticmethod
    def tiny():
        """One period (softmax, then three delta-rule layers) at a quarter
        of the heads of each kind and half of the experts."""
        return SolarConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=8,
            gqa_layers=(0, 4), num_attention_heads=8, num_key_value_heads=4,
            head_dim=16, linear_num_heads=8, linear_head_dim=16,
            kda_chunk_size=16, intermediate_size=128,
            moe_intermediate_size=32, n_routed_experts=8,
            num_experts_per_tok=2, seq_len=32, experts_held=4,
            expert_offset=4, heads_held=2, kv_heads_held=1,
            linear_heads_held=2, num_layers_held=4)


def kda_attention(x, cfg: SolarConfig, pre: str):
    """Kimi-delta attention over the held heads: q, k, v = silu(conv(x W));
    q, k L2-normed a head (q times head_dim^-0.5); the per-channel log decay
    g = -exp(A_log) softplus((x W_f1) W_f2 + dt_bias), `W_f1` [hidden,
    head_dim] whole on every chip; beta = 2 sigmoid(x W_beta)
    (`kda_allow_neg_eigval`; 1 sigmoid without); the gated delta rule for
    any g <= 0; RMSNorm over a head (one weight of `head_dim`) times
    sigmoid((x W_g1) W_g2 + b_g), an element; W_o. The builder's initial
    `A_log` and `dt_bias` are constants; a trainer that wants the family's
    seeded draws sets them in the scope."""
    nh = cfg.held("linear_heads_held", "linear_num_heads")
    hd, s = cfg.linear_head_dim, cfg.seq_len
    width = nh * hd
    with name_scope("kda.proj"):
        qkv = [_linear(x, width, pre + f"{n}_proj_w", cfg) for n in "qkv"]
        decay = _linear(_linear(x, hd, pre + "f_a_proj_w", cfg), width,
                        pre + "f_b_proj_w", cfg)
        beta = _linear(x, nh, pre + "b_proj_w", cfg)
        gate = layers.fc(
            _linear(x, hd, pre + "g_a_proj_w", cfg), width,
            num_flatten_dims=2, param_attr=_w(pre + "g_b_proj_w", cfg),
            bias_attr=ParamAttr(name=pre + "g_b_proj_b",
                                initializer=I.Constant(0.0)))
    q, k, v = short_conv_heads(qkv, cfg, pre, nh, hd)
    with name_scope("kda.gate"):
        g = layers.kda_gate(
            decay,
            layers.create_parameter(
                [nh], "float32", attr=ParamAttr(
                    name=pre + "A_log", initializer=I.Constant(0.0))),
            layers.create_parameter(
                [width], "float32", attr=ParamAttr(
                    name=pre + "dt_bias", initializer=I.Constant(0.0))))
    with name_scope("kda.scan"):
        o = layers.kda_scan(
            q, k, v, g, beta, cfg.kda_chunk_size,
            beta_scale=2.0 if cfg.kda_allow_neg_eigval else 1.0)
    with name_scope("kda.out"):
        o = layers.head_gate(_norm(o, pre + "o_norm_scale", cfg),
                             layers.reshape(gate, [0, s, nh, hd]))
        return _linear(layers.reshape(o, [0, s, width]), cfg.hidden_size,
                       pre + "o_proj_w", cfg)


def gated_grouped_attention(x, cfg: SolarConfig, pre: str):
    """The held query heads on the held KV heads, no rotary positions, no
    per-head norm, causal over the whole row, the heads' outputs under an
    element-wise sigmoid gate from the layer's input (`use_gqa_gate`)."""
    return causal_lm.grouped_attention(
        x, cfg, pre, cfg.held("heads_held", "num_attention_heads"),
        cfg.held("kv_heads_held", "num_key_value_heads"),
        gate=cfg.use_gqa_gate)


def expert_layer(x, cfg: SolarConfig, pre: str):
    """(this rank's routed part + the shared expert, top_idx,
    expert_load): sigmoid scores over ALL `n_routed_experts`, the top
    `num_experts_per_tok` of score + bias (a buffer, 0 and moved by no
    step), no groups, their weights divided by their sum and scaled; gated
    experts of `moe_intermediate_size`, the shared one of the same width."""
    return causal_lm.expert_layer(
        x, cfg, pre, experts_total=cfg.n_routed_experts,
        routed_scaling=cfg.routed_scaling_factor,
        shared=(gated_ffn, cfg.moe_intermediate_size * cfg.n_shared_experts))


def decoder_layer(x, cfg: SolarConfig, n: int):
    """Published layer n: (x_out, (top_idx, expert_load))."""
    pre = f"l{n}_"
    attend = gated_grouped_attention if cfg.kind(n) == GQA else kda_attention
    x = layers.elementwise_add(
        x, attend(_norm(x, pre + "attn_norm_scale", cfg), cfg, pre))
    y, idx, load = expert_layer(_norm(x, pre + "ffn_norm_scale", cfg), cfg,
                                pre)
    return layers.elementwise_add(x, y), (idx, load)


def build_causal_lm_program(cfg: SolarConfig):
    """Next-token objective over `tokens` [B, seq_len]
    (`causal_lm.build_causal_lm_program`) of the layers held: (tokens,
    loss, routed), `routed` the `(top_idx, expert_load)` variables of each
    layer."""
    return causal_lm.build_causal_lm_program(
        cfg, "solar", decoder_layer, cfg.layers_here())


def sharding_rules() -> ShardingRules:
    """tp / ep rules as data: the projections into heads column-parallel
    (q, k, v of both kinds, the attention's gate, KDA's beta and the second
    factor of its two low-rank pairs), their conv kernels and per-head
    parameters with them, the output projections row-parallel, the shared
    expert by its width, the experts' leading dim over `ep`, the vocabulary
    over `tp`. The low-rank pairs' first factors, the router and the norms
    stay whole on every chip."""
    return causal_lm.sharding_rules([
        (r"_(q|k|v|b|g|f_b|g_b)_proj_w$", P(None, "tp")),
        (r"_(q|k|v)_conv_w$", P(None, "tp")),
        (r"_(A_log|dt_bias|g_b_proj_b)$", P("tp")),
        (r"_shared_(gate|up)_w$", P(None, "tp")),
        (r"_shared_down_w$", P("tp", None)),
    ])
