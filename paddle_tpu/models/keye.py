"""Keye-VL-2.0-family language model (`model_type` KeyeVL2), static-graph
builder: a decoder whose every layer attends over a LEARNED selection of
keys. Beside the main attention (32 query heads on 4 KV heads) each layer
has an indexer (`sa_config`: a few small query heads, one key head, a weight
a head): it scores every causal (query, key) pair, the query attends the
`topk` best-scored keys only, all its heads the same ones, and the indexer
is trained towards the attention's own head-summed probabilities on them.
Rotary positions come in three streams (`rope_scaling.mrope_section`: the
pairs of a head shared out among time, height and width positions); on a
text row the three are the row's own positions. Every layer has softmax-
routed experts, top-8 of 128, weights normalised, no bias, no shared
expert, no dense layer, exactly as `models/mellum.py` calls them.

What a selection is: an int8 variable [B, S, S], 1 where query t attends
key s, made by `layers.sparse_index`, handed to `layers.fused_attention` as
its `select` input; one a row, whatever the head count; data, where
`models/mellum.py`'s window is an attribute.

The objective is the next-token loss plus every layer's indexer loss
(`causal_lm.build_causal_lm_program(auxiliary=...)`). The indexer reads the
layer's normed input DETACHED (`layers.detach`), and the attention's
probabilities reach its loss as a target, so the trunk learns from the
next-token loss alone and the indexer from its own loss alone.

The configuration's keys are the published `config.json`'s (`sa_config`'s
flattened: `indexer_num_heads`, `indexer_head_dim`, `index_topk`). What one
expert-parallel rank holds is said beside them, as in `models/mellum.py`.
The vision tower is not built: the published configuration row holds the
language model's keys only; `positions` feeds the three streams where a
caller has them.

Device work carries scopes: `attn.proj`, `attn.attend.sparse`, and the
indexer's `attn.index.score` (its projections and rotary here; the products
name themselves), `attn.index.select`, `attn.index.target`,
`attn.index.loss`; the routed op names its own (`moe.*`).
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
from .causal_lm import _heads, _linear, _norm, record_expert_load

__all__ = ["KeyeConfig", "build_causal_lm_program", "record_expert_load",
           "record_selection", "sharding_rules"]


@dataclass
class KeyeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    # pairs of a head by position stream (time, height, width)
    mrope_section: tuple = (16, 24, 24)
    # `sa_config`: the indexer
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    index_topk: int = 2048
    indexer_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    seq_len: int = 8192
    # three position streams [3, B, seq_len] as a feed "positions" (rows of
    # image patches); False: text rows, every stream the row's own positions
    position_streams: bool = False
    # this rank's share of every expert layer (None: all the experts)
    experts_held: "int | None" = None
    expert_offset: int = 0

    @staticmethod
    def tiny():
        """`index_topk` under the row and no multiple of a kernel block:
        early queries take every key, late ones select."""
        return KeyeConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
            mrope_section=(2, 2, 4), indexer_num_heads=2, indexer_head_dim=8,
            index_topk=12, seq_len=32)


def _rotary(t, cfg: KeyeConfig, positions):
    """Half-split rotary over all of t's features, `mrope_section` scaled
    to its width (a head of 64 gets half the pairs of a head of 128)."""
    half = int(t.shape[-1]) // 2
    share = sum(cfg.mrope_section)
    sections = [n * half // share for n in cfg.mrope_section]
    return layers.rotary_embedding(t, theta=cfg.rope_theta, layout="half",
                                   positions=positions, sections=sections)


def indexer(a, cfg: KeyeConfig, pre: str, positions=None):
    """(scores, select, pairs) of one layer's indexer from the layer's
    normed input `a`, read detached: `indexer_num_heads` queries of
    `indexer_head_dim`, one key head under a LayerNorm, both turned by the
    rotary rule, a weight a head times heads^-0.5 head_dim^-0.5."""
    nh, hd = cfg.indexer_num_heads, cfg.indexer_head_dim
    with name_scope("attn.index.score"):
        a = layers.detach(a)
        q = _rotary(_heads(_linear(a, nh * hd, pre + "indexer_q_w", cfg),
                           nh, hd), cfg, positions)
        k = _rotary(layers.layer_norm(
            _linear(a, hd, pre + "indexer_k_w", cfg), begin_norm_axis=2,
            epsilon=cfg.indexer_norm_eps,
            param_attr=ParamAttr(name=pre + "indexer_k_norm_scale"),
            bias_attr=ParamAttr(name=pre + "indexer_k_norm_bias",
                                initializer=I.Constant(0.0))),
            cfg, positions)
        w = layers.scale(_linear(a, nh, pre + "indexer_head_w", cfg),
                         scale=nh ** -0.5 * hd ** -0.5)
    with name_scope("dsa.io"):
        return layers.sparse_index(q, k, w, cfg.index_topk)


def decoder_layer(x, cfg: KeyeConfig, n: int, positions, index_losses,
                  selections):
    """Layer n: (x_out, (top_idx, expert_load)); the indexer's loss is
    appended to `index_losses`, and (the selection, its mean count of keys a
    query) to `selections`."""
    pre = f"l{n}_"
    a = _norm(x, pre + "attn_norm_scale", cfg)
    scores, select, pairs = indexer(a, cfg, pre, positions)
    attended, target = causal_lm.grouped_attention(
        a, cfg, pre, cfg.num_attention_heads, cfg.num_key_value_heads,
        rotary=lambda t: _rotary(t, cfg, positions), selection=select)
    with name_scope("dsa.io"):
        index_losses.append(layers.sparse_index_loss(scores, select, target))
        # a copy nothing reads: fetchable where the layer is a recomputed
        # segment, gone from the compiled step where it is not fetched
        selections.append((layers.assign(select), pairs))
    x = layers.elementwise_add(x, attended)
    y, idx, load = causal_lm.expert_layer(
        _norm(x, pre + "ffn_norm_scale", cfg), cfg, pre,
        experts_total=cfg.num_experts, scoring="softmax", select_bias=False)
    return layers.elementwise_add(x, y), (idx, load)


def build_causal_lm_program(cfg: KeyeConfig):
    """Next-token objective plus every layer's indexer loss over `tokens`
    [B, seq_len] (`causal_lm.build_causal_lm_program`): (tokens, loss,
    routed). The loss carries `_auxiliary_losses` (the indexer losses, a
    layer each), `_selections` (each layer's selection [B, S, S] int8) and
    `_selected_pairs` (each layer's mean count of selected keys a query,
    [1]), all fetchable beside it; the next-token loss alone is the loss
    less the indexer losses."""
    positions = None
    if cfg.position_streams:
        positions = layers.data(name="positions", shape=[3, -1, cfg.seq_len],
                                dtype="int64", append_batch_size=False)
    index_losses, selections = [], []

    def layer(x, cfg, n):
        return decoder_layer(x, cfg, n, positions, index_losses, selections)

    tokens, loss, routed = causal_lm.build_causal_lm_program(
        cfg, "keye", layer, range(cfg.num_hidden_layers),
        auxiliary=index_losses)
    loss._selections = [select for select, _ in selections]
    loss._selected_pairs = [pairs for _, pairs in selections]
    return tokens, loss, routed


def record_selection(pairs) -> float:
    """The selections of one dispatch into the program's metrics, from the
    `_selected_pairs` values fetched beside its losses (any leading axes:
    layer, step): gauge `attn.selected_pairs_per_query`, the mean count of
    keys a query attends (sum_t min(t + 1, topk) / seq_len when every
    selection is full). Returns it."""
    import numpy as np
    from ..observability import metrics
    mean = float(np.asarray(pairs, np.float64).mean())
    metrics.set_gauge("attn.selected_pairs_per_query", mean)
    return mean


def sharding_rules() -> ShardingRules:
    """tp / ep rules as data, `models/mellum.py`'s: q, k, v column-parallel
    by head, the output projection row-parallel, the experts over `ep`, the
    vocabulary over `tp`. The indexer is small and whole on every rank: its
    selection has to be the same for all the heads of a row."""
    return causal_lm.sharding_rules([(r"_(q|k|v)_proj_w$", P(None, "tp"))])
