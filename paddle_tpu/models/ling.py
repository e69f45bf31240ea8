"""Ling-3.0-family hybrid causal LM (`model_type` bailing_hybrid),
static-graph builder: a decoder whose layers come in groups of
`layer_group_size`, the last of a group with latent attention (MLA) and the
others with Kimi-delta linear attention (KDA: three short causal convs, an
L2 norm on q and k, a bounded per-channel decay, the gated delta rule in
its chunked form, a per-head RMS norm), every attention output under a
head-wise sigmoid gate; a SwiGLU feed-forward part in the first
`first_k_dense_replace` layers and sigmoid-routed experts picked inside the
best `topk_group` of `n_group` groups, with a shared expert, from there on.

The configuration's keys are the published `config.json`'s. What one chip
of a deployment holds is said beside them, as in `models/deepseek_v3.py`:
`experts_held` experts from `expert_offset` of the `num_experts` the router
scores; `heads_held` of the `num_attention_heads` a layer has (the
projections into heads are built for the held heads only, and the output
projection's rows with them; heads are alike to the program, so which of
them these are is the checkpoint loader's business and no key here); layers
`first_layer` .. `first_layer + num_layers_held` of `num_hidden_layers`
(a layer's kind follows its published index); a sliced vocabulary is a
smaller `vocab_size`. On one chip the routed part and the attention output
are this chip's share of their sums and nothing stands in for the others.

Ops of the Program IR only, unrolled. Layer boundaries land on the loss's
`_layer_checkpoints`. Device work carries `program.name_scope` names:
`kda.proj`, `kda.conv`, `kda.gate`, `kda.scan`, `kda.out`; `mla.proj`,
`mla.attend`; `moe.shared`; the routed op names its own (`moe.route`,
`moe.dispatch`, `moe.experts`, `moe.combine`).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

from jax.sharding import PartitionSpec as P

from .. import layers
from .. import initializer as I
from ..framework.program import name_scope
from ..layer_helper import ParamAttr
from ..parallel.mesh import ShardingRules
from . import causal_lm
from .causal_lm import (_heads, _linear, _norm, dense_ffn, gated_ffn,
                        record_expert_load, short_conv_heads)

__all__ = ["LingConfig", "build_causal_lm_program", "record_expert_load",
           "sharding_rules"]

KDA, LATENT = "kda", "latent"


@dataclass
class LingConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    num_hidden_layers: int = 42
    layer_group_size: int = 6
    first_k_dense_replace: int = 2
    num_attention_heads: int = 32
    head_dim: int = 128
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kda_chunk_size: int = 64
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    num_experts: int = 512
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    # the experts' and the shared expert's activation clamp by layer; a
    # layer whose entry is not 0 is refused (the clamp's form is not in
    # config.json)
    expert_swiglu_limit_list: tuple = ()
    share_expert_swiglu_limit_list: tuple = ()
    rms_norm_eps: float = 1e-6
    rope_theta: float = 6000000.0
    initializer_range: float = 0.02
    seq_len: int = 8192
    # this chip's share (None: everything)
    experts_held: "int | None" = None
    expert_offset: int = 0
    heads_held: "int | None" = None
    first_layer: int = 0
    num_layers_held: "int | None" = None

    def kind(self, n: int) -> str:
        """The attention of published layer n: the last layer of a group of
        `layer_group_size` is the softmax one."""
        return LATENT if (n + 1) % self.layer_group_size == 0 else KDA

    def layers_here(self) -> range:
        held = (self.num_hidden_layers - self.first_layer
                if self.num_layers_held is None else self.num_layers_held)
        return range(self.first_layer, self.first_layer + held)

    def heads(self) -> int:
        held = self.heads_held or self.num_attention_heads
        if held > self.num_attention_heads:
            raise ValueError(
                f"{held} heads held of {self.num_attention_heads}")
        return held

    @staticmethod
    def tiny():
        return LingConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=12,
            layer_group_size=3, first_k_dense_replace=2,
            num_attention_heads=4, head_dim=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
            kda_chunk_size=16, intermediate_size=128,
            moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
            num_experts=16, num_experts_per_tok=2, n_group=4, topk_group=2,
            seq_len=32, first_layer=1, num_layers_held=4)


def kda_attention(x, cfg: LingConfig, pre: str):
    """Kimi-delta attention over the held heads: q, k, v = silu(conv(x W));
    q, k L2-normed a head (q times head_dim^-0.5); the per-channel log decay
    g = lower_bound * sigmoid(exp(A_log) * (x W_f + dt_bias)); beta =
    sigmoid(x W_beta); the gated delta rule; RMSNorm over a head (one
    weight of `head_dim`) times sigmoid(x W_g), a scalar a head; W_o. The
    builder's initial `A_log` and `dt_bias` are constants; a trainer that
    wants the family's seeded draws sets them in the scope."""
    nh, hd, s = cfg.heads(), cfg.head_dim, cfg.seq_len
    width = nh * hd
    with name_scope("kda.proj"):
        qkv = [_linear(x, width, pre + f"{n}_proj_w", cfg) for n in "qkv"]
        decay = _linear(x, width, pre + "f_proj_w", cfg)
        beta = _linear(x, nh, pre + "b_proj_w", cfg)
        gate = _linear(x, nh, pre + "g_proj_w", cfg)
    q, k, v = short_conv_heads(qkv, cfg, pre, nh, hd)
    with name_scope("kda.gate"):
        g = layers.kda_gate(
            decay,
            layers.create_parameter(
                [nh], "float32", attr=ParamAttr(
                    name=pre + "A_log", initializer=I.Constant(0.0))),
            layers.create_parameter(
                [width], "float32", attr=ParamAttr(
                    name=pre + "dt_bias", initializer=I.Constant(0.0))),
            cfg.kda_lower_bound)
    with name_scope("kda.scan"):
        o = layers.kda_scan(q, k, v, g, beta, cfg.kda_chunk_size,
                            lower_bound=cfg.kda_lower_bound)
    with name_scope("kda.out"):
        o = layers.head_gate(_norm(o, pre + "o_norm_scale", cfg), gate)
        return _linear(layers.reshape(o, [0, s, width]), cfg.hidden_size,
                       pre + "o_proj_w", cfg)


def gated_latent_attention(x, cfg: LingConfig, pre: str):
    """Latent attention (MLA without the query's low-rank step) over the
    held heads, as this family runs it: an RMS norm a head on q and on
    k = [k_nope | k_pe] (a weight of qk_nope + qk_rope each) BEFORE the
    rotary turn of their last `qk_rope_head_dim` features, and the
    head-wise sigmoid gate on the output. A sibling of
    `deepseek_v3.latent_attention` and not an option of it: there the
    shared rotary key is turned once, before it is repeated to the heads;
    here every head norms its own [k_nope | k_pe] first, so the turn comes
    after the concat, head by head."""
    nh, nope, rope = cfg.heads(), cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    vd, lora, s = cfg.v_head_dim, cfg.kv_lora_rank, cfg.seq_len
    with name_scope("mla.proj"):
        q = _heads(_linear(x, nh * (nope + rope), pre + "q_proj_w", cfg),
                   nh, nope + rope)
        kva = _linear(x, lora + rope, pre + "kv_a_proj_w", cfg)
        c, k_pe = layers.split(kva, [lora, rope], dim=2)
        kv = _heads(_linear(_norm(c, pre + "kv_a_norm_scale", cfg),
                            nh * (nope + vd), pre + "kv_b_proj_w", cfg),
                    nh, nope + vd)
        k_nope, v = layers.split(kv, [nope, vd], dim=3)
        k = layers.concat(
            [k_nope, layers.expand(layers.reshape(k_pe, [0, 1, s, rope]),
                                   [1, nh, 1, 1])], axis=3)
        q, k = (layers.rotary_embedding(
            _norm(t, pre + f"{n}_norm_scale", cfg), theta=cfg.rope_theta,
            rotary_dim=rope) for n, t in (("q", q), ("k", k)))
        gate = _linear(x, nh, pre + "g_proj_w", cfg)
    with name_scope("mla.attend"):
        ctx = layers.fused_attention(
            q, k, v, causal=True, scale=1.0 / math.sqrt(nope + rope))
    with name_scope("mla.proj"):
        ctx = layers.head_gate(layers.transpose(ctx, [0, 2, 1, 3]), gate)
        return _linear(layers.reshape(ctx, [0, 0, nh * vd]),
                       cfg.hidden_size, pre + "o_proj_w", cfg)


def _no_clamp(limits, n, what):
    if n < len(limits) and limits[n] != 0:
        raise ValueError(
            f"layer {n}: {what} = {limits[n]}; the clamp's form is not in "
            "config.json and no layer with one is built")


def expert_layer(x, cfg: LingConfig, pre: str, n: int):
    """(this rank's routed part + the shared expert, top_idx,
    expert_load): sigmoid scores over ALL `num_experts`, the top
    `num_experts_per_tok` of score + bias among the experts of the best
    `topk_group` of `n_group` groups, their weights divided by their sum
    and scaled; gated experts."""
    _no_clamp(cfg.expert_swiglu_limit_list, n, "expert_swiglu_limit_list")
    _no_clamp(cfg.share_expert_swiglu_limit_list, n,
              "share_expert_swiglu_limit_list")
    return causal_lm.expert_layer(
        x, cfg, pre, experts_total=cfg.num_experts,
        routed_scaling=cfg.routed_scaling_factor, n_group=cfg.n_group,
        topk_group=cfg.topk_group,
        shared=(gated_ffn, cfg.moe_shared_expert_intermediate_size))


def decoder_layer(x, cfg: LingConfig, n: int):
    """Published layer n: (x_out, (top_idx, expert_load) or None)."""
    pre = f"l{n}_"
    attend = (gated_latent_attention if cfg.kind(n) == LATENT
              else kda_attention)
    x = layers.elementwise_add(
        x, attend(_norm(x, pre + "attn_norm_scale", cfg), cfg, pre))
    f = _norm(x, pre + "ffn_norm_scale", cfg)
    if n < cfg.first_k_dense_replace:
        return layers.elementwise_add(
            x, dense_ffn(f, cfg.intermediate_size, pre + "mlp_", cfg)), None
    y, idx, load = expert_layer(f, cfg, pre, n)
    return layers.elementwise_add(x, y), (idx, load)


def build_causal_lm_program(cfg: LingConfig):
    """Next-token objective over `tokens` [B, seq_len]
    (`causal_lm.build_causal_lm_program`) of the layers held: (tokens,
    loss, routed), `routed` the `(top_idx, expert_load)` variables of each
    expert layer."""
    return causal_lm.build_causal_lm_program(
        cfg, "ling", decoder_layer, cfg.layers_here())


def sharding_rules() -> ShardingRules:
    """tp / ep rules as data: the projections into heads column-parallel
    (KDA's q, k, v, decay, beta and gate; MLA's q and kv_b and its gate),
    their conv kernels and per-head parameters with them, the output
    projections row-parallel, the dense and shared feed-forward parts by
    their width, the experts' leading dim over `ep`, the vocabulary over
    `tp`. `kv_a_proj` and the norms stay whole on every chip."""
    return causal_lm.sharding_rules([
        (r"_(q|k|v|f|b|g|kv_b)_proj_w$", P(None, "tp")),
        (r"_(q|k|v)_conv_w$", P(None, "tp")),
        (r"_(A_log|dt_bias)$", P("tp")),
        (r"_(mlp|shared)_(gate|up)_w$", P(None, "tp")),
        (r"_(mlp|shared)_down_w$", P("tp", None)),
    ])
