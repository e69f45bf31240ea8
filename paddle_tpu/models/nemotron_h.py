"""Nemotron-H-family hybrid causal LM (`model_type` nemotron_h),
static-graph builder: a decoder whose every layer is ONE mixer or ONE
feed-forward part under one pre-norm and one residual add, the kind read
from `hybrid_override_pattern` letter by letter: `M` a Mamba-2 state-space
mixer (input projection, causal depthwise conv, selective scan in its
chunked form, gated grouped RMS norm, output projection), `E` sigmoid-routed
experts of the form W_down relu(W_up x)^2 with a selection bias and a shared
expert of the same form, `*` attention on grouped KV heads without rotary
positions (the family takes positions from its state-space layers).

The configuration's keys are the published `config.json`'s. What one
expert-parallel rank holds is said beside them, as in
`models/deepseek_v3.py`: `experts_held` experts from `expert_offset` of the
`n_routed_experts` the router scores; `mamba_heads_held` heads in
`mamba_groups_held` whole B/C groups of a state-space mixer, and
`heads_held` query heads on the `kv_heads_held` KV heads they read (the
projections into heads are built for the held heads only, the output
projection's rows with them; heads are alike to the program, so which of
them these are is the checkpoint loader's business and no key here); a
sliced vocabulary is a smaller `vocab_size`. On one chip the routed part
and a mixer's output are this chip's share of their sums and nothing stands
in for the other chips.

`moe_latent_size` (None in the family's older members): the routed experts
live in a latent of that width, z = x W_a in front of the dispatch and
(sum_k w_k E_k(z)) W_b behind the combine; the router and the shared expert
read x at the full width.

Ops of the Program IR only, unrolled. Layer boundaries land on the loss's
`_layer_checkpoints`. Device work carries `program.name_scope` names:
`ssm.in_proj`, `ssm.conv`, `ssm.scan`, `ssm.gate_norm`, `ssm.out_proj`;
`attn.proj`, `attn.attend.full`; `moe.shared`, `moe.latent_down`,
`moe.latent_up`; the routed op names its own (`moe.route`, `moe.dispatch`,
`moe.experts`, `moe.combine`).
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
from .causal_lm import _linear, _norm, _w, record_expert_load, relu2_ffn

__all__ = ["NemotronHConfig", "build_causal_lm_program",
           "record_expert_load", "sharding_rules"]

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


@dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    # kind of layer n: its n-th letter
    hybrid_override_pattern: str = (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    # the routed experts' own width (None: hidden_size)
    moe_latent_size: "int | None" = None
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    seq_len: int = 8192
    # this chip's share of every layer (None: everything)
    experts_held: "int | None" = None
    expert_offset: int = 0
    mamba_heads_held: "int | None" = None
    mamba_groups_held: "int | None" = None
    heads_held: "int | None" = None
    kv_heads_held: "int | None" = None

    def kind(self, n: int) -> str:
        return self.hybrid_override_pattern[n]

    def mamba_share(self) -> tuple:
        """(heads, B/C groups) a state-space mixer is built for: whole
        groups, each with all its heads (the gated norm's statistics are a
        group's, and B and C are shared by a group's heads)."""
        hm = self.mamba_heads_held or self.mamba_num_heads
        g = self.mamba_groups_held or self.n_groups
        if (g > self.n_groups
                or hm * self.n_groups != g * self.mamba_num_heads):
            raise ValueError(
                f"{hm} heads in {g} groups held of {self.mamba_num_heads} "
                f"in {self.n_groups}: a share is whole groups")
        return hm, g

    def attention_share(self) -> tuple:
        """(query heads, KV heads) an attention layer is built for: the
        held KV heads with all their query heads, or, where fewer chips
        than that hold a KV head each, one KV head with some of its."""
        nh = self.heads_held or self.num_attention_heads
        nkv = self.kv_heads_held or self.num_key_value_heads
        group = self.num_attention_heads // self.num_key_value_heads
        if (nkv > self.num_key_value_heads
                or not (nh == nkv * group or nkv == 1 and 0 < nh <= group)):
            raise ValueError(
                f"{nh} query heads on {nkv} KV heads held of "
                f"{self.num_attention_heads} on {self.num_key_value_heads}")
        return nh, nkv

    @staticmethod
    def tiny():
        return NemotronHConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=9,
            hybrid_override_pattern="MEMEM*EME", num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
            mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=8,
            moe_intermediate_size=32, moe_shared_expert_intermediate_size=64,
            n_routed_experts=8, num_experts_per_tok=2, seq_len=32)

    @staticmethod
    def tiny_latent_share():
        """`tiny()`'s twin with the experts in a latent, more slots a token
        than experts held (6 of 32 with 4 held) and one chip's share of the
        heads: one B/C group of two with its 4 heads, 2 of 8 query heads on
        one KV head of two."""
        return NemotronHConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=9,
            hybrid_override_pattern="MEMEM*EME", num_attention_heads=8,
            num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
            mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=8,
            moe_intermediate_size=32, moe_shared_expert_intermediate_size=64,
            moe_latent_size=24, n_routed_experts=32, num_experts_per_tok=6,
            routed_scaling_factor=5.0, seq_len=32, experts_held=4,
            mamba_heads_held=4, mamba_groups_held=1, heads_held=2,
            kv_heads_held=1)


def _per_head(name, heads, initializer):
    return layers.create_parameter(
        [heads], "float32",
        attr=ParamAttr(name=name, initializer=initializer))


def mamba_mixer(x, cfg: NemotronHConfig, pre: str):
    """Mamba-2: [z | xBC | dt] = x W_in; xBC through the causal conv and
    silu; the selective scan over x [H, P] with B, C [G, N]; the gated
    grouped norm with z; W_out. H and G are the held heads and groups
    (`mamba_share`): W_in's columns [z | x | B | C | dt] of those alone,
    W_out's rows with them. The builder's initial values of `dt_bias`,
    `A_log`, `D` are constants (a step of 0.01, A = -1, D = 1); a trainer
    that wants the family's seeded draws sets them in the scope."""
    (hm, g), p, n = cfg.mamba_share(), cfg.mamba_head_dim, cfg.ssm_state_size
    d_in, s = hm * p, cfg.seq_len
    with name_scope("ssm.in_proj"):
        zxbcdt = _linear(x, 2 * d_in + 2 * g * n + hm, pre + "in_proj_w", cfg)
        z, xbc, dt = layers.split(zxbcdt, [d_in, d_in + 2 * g * n, hm], dim=2)
    with name_scope("ssm.conv"):
        xbc = layers.causal_conv1d(
            xbc, cfg.conv_kernel, param_attr=_w(pre + "conv_w", cfg),
            bias_attr=ParamAttr(name=pre + "conv_b"), activation="silu")
        xs, b, c = layers.split(xbc, [d_in, g * n, g * n], dim=2)
    with name_scope("ssm.scan"):
        y = layers.ssm_scan(
            layers.reshape(xs, [0, s, hm, p]),
            layers.reshape(b, [0, s, g, n]), layers.reshape(c, [0, s, g, n]),
            dt,
            _per_head(pre + "dt_bias", hm,
                      I.Constant(math.log(math.expm1(0.01)))),
            _per_head(pre + "A_log", hm, I.Constant(0.0)),
            _per_head(pre + "D", hm, I.Constant(1.0)),
            chunk_size=cfg.chunk_size)
    with name_scope("ssm.gate_norm"):
        y = layers.gated_group_rms_norm(
            layers.reshape(y, [0, s, d_in]), z, groups=g,
            epsilon=cfg.layer_norm_epsilon,
            param_attr=ParamAttr(name=pre + "ssm_norm_scale"))
    with name_scope("ssm.out_proj"):
        return _linear(y, cfg.hidden_size, pre + "out_proj_w", cfg)


def expert_layer(x, cfg: NemotronHConfig, pre: str):
    """(this rank's routed part + the shared expert, top_idx,
    expert_load): sigmoid scores over ALL `n_routed_experts`, the top
    `num_experts_per_tok` of score + bias, their weights divided by their
    sum and scaled; experts without a gate. With `moe_latent_size` the
    experts read z = x W_a and what they sum goes through W_b; the router
    and the shared expert read x."""
    def into(x):
        with name_scope("moe.latent_down"):
            return _linear(x, cfg.moe_latent_size, pre + "latent_down_w", cfg)

    def out_of(routed):
        with name_scope("moe.latent_up"):
            return _linear(routed, cfg.hidden_size, pre + "latent_up_w", cfg)

    return causal_lm.expert_layer(
        x, cfg, pre, experts_total=cfg.n_routed_experts, gated=False,
        routed_scaling=cfg.routed_scaling_factor,
        latent=(cfg.moe_latent_size, into, out_of) if cfg.moe_latent_size
        else None,
        shared=(relu2_ffn, cfg.moe_shared_expert_intermediate_size))


def grouped_attention(x, cfg: NemotronHConfig, pre: str):
    """`num_attention_heads` query heads on `num_key_value_heads` KV heads
    (query head h attends KV head h // group), causal, no rotary positions;
    built for the held query heads and the KV heads they read
    (`attention_share`). K and V go to the attention op at their own head
    count."""
    return causal_lm.grouped_attention(x, cfg, pre, *cfg.attention_share())


def decoder_layer(x, cfg: NemotronHConfig, n: int):
    """x + Mixer_n(RMSNorm(x)); (x_out, (top_idx, expert_load) or None)."""
    pre, kind = f"l{n}_", cfg.kind(n)
    a = _norm(x, pre + "norm_scale", cfg)
    routed = None
    if kind == MAMBA:
        y = mamba_mixer(a, cfg, pre)
    elif kind == EXPERTS:
        y, idx, load = expert_layer(a, cfg, pre)
        routed = (idx, load)
    elif kind == ATTENTION:
        y = grouped_attention(a, cfg, pre)
    else:
        raise ValueError(f"hybrid_override_pattern: no layer kind {kind!r}")
    return layers.elementwise_add(x, y), routed


def build_causal_lm_program(cfg: NemotronHConfig):
    """Next-token objective over `tokens` [B, seq_len]
    (`causal_lm.build_causal_lm_program`): (tokens, loss, routed), `routed`
    the `(top_idx, expert_load)` variables of each expert layer."""
    return causal_lm.build_causal_lm_program(
        cfg, "nemotron_h", decoder_layer, range(cfg.num_hidden_layers))


def sharding_rules() -> ShardingRules:
    """tp / ep rules as data: q, k, v column-parallel by head, the output
    projections row-parallel, the shared expert by its width, the experts'
    leading dim over `ep`, the vocabulary over `tp`. k and v split by KV
    head: `tp` may not pass `num_key_value_heads` (2 as published). The
    state-space mixer has no rule here and stays whole under these: its
    input projection's columns are [z | x | B | C | dt], and B and C are
    shared by the heads of a group, so a plain split of the columns is no
    share. A GROUP a chip is: that group's z, x, B, C and dt columns, its
    rows of the output projection, its slice of the gated norm, which is
    what `mamba_heads_held` / `mamba_groups_held` build and a rule would
    have to say by column ranges. Where more chips share a layer's heads
    than it has KV heads, a KV head lives on several of them, each with
    some of its query heads (`heads_held` / `kv_heads_held`)."""
    return causal_lm.sharding_rules([
        (r"_(q|k|v)_proj_w$", P(None, "tp")),
        (r"_shared_up_w$", P(None, "tp")),
        (r"_shared_down_w$", P("tp", None)),
    ])
