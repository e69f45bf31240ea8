"""Operations and bytes a step of a decoder LM NEEDS whose attention runs
over a learned selection of keys (an indexer of a few small heads scores
every causal pair, a query attends its `topk` best-scored keys, all query
heads the same ones) on grouped KV heads, with sparse experts, as functions
of shapes (`counts.py` has the rules: what the mathematics requires, nothing
an implementation adds). The main attention needs the SELECTED pairs and no
others, whatever a kernel computes; the indexer needs every causal pair
forward (it has to score a pair to leave it out) and the selected pairs
backward (its loss lives on them); its target is one product over the
selected pairs and has no backward; its projections have no gradient into
their input (the indexer reads it detached). A forward run twice under
recomputation is the implementation's and not counted.

Keys are the configuration file's: the published `config.json` names
(`sa_config` a group), with `layers` the depth run, `vocab` the vocabulary
rows held, `num_experts` the experts held of `experts_total`.
"""
from __future__ import annotations

from .counts_window_gqa import (attend_pairs, gated_ffn_flops_per_token,
                                gqa_proj_flops_per_token)


def causal_pairs(seq: int) -> int:
    """(query, key) pairs of one row, query t on keys 0..t."""
    return attend_pairs(seq)


def selected_pairs(cfg: dict, seq: int) -> int:
    """Pairs of one row a full selection keeps: query t its min(t + 1, topk)
    best keys. The count of a causal window of `topk`, at other places."""
    return attend_pairs(seq, cfg["sa_config"]["topk"])


def index_proj_flops_per_token(cfg: dict) -> int:
    """The indexer's three projections: queries, one key head, a weight a
    head."""
    sa = cfg["sa_config"]
    nh, hd = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return 2 * cfg["hidden_size"] * (nh * hd + hd + nh)


def index_score_flops(cfg: dict, pairs: float) -> float:
    """One product of the indexer's heads over `pairs` (query, key) pairs."""
    sa = cfg["sa_config"]
    return 2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"] * pairs


def target_flops(cfg: dict, pairs: float) -> float:
    """q . k of every query head once more over `pairs` pairs: what the
    head-summed probabilities need beside the logsumexp the attention
    kept."""
    return 2.0 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs


def attend_flops(cfg: dict, pairs: float) -> float:
    """QK^T and PV of every query head over `pairs` pairs."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs


def lm_train_flops_per_token(cfg: dict, seq: int,
                             local_assign_per_tok: float) -> float:
    """Forward + backward of one token. Three times the forward (a gradient
    for each operand of each matmul) for the projections, the attention
    over the selected pairs, the router, the routed experts at
    `local_assign_per_tok` assignments a token that really fell on a held
    expert, and the head over the vocabulary held. The indexer: its
    projections twice (no gradient into their input), its scores over the
    causal pairs forward and twice over the selected pairs backward, its
    target once over the selected pairs."""
    h = cfg["hidden_size"]
    kept, causal = selected_pairs(cfg, seq) / seq, causal_pairs(seq) / seq
    trunk = (gqa_proj_flops_per_token(cfg) + attend_flops(cfg, kept)
             + 2 * h * cfg["experts_total"] + local_assign_per_tok
             * gated_ffn_flops_per_token(h, cfg["moe_intermediate_size"]))
    indexer = (2 * index_proj_flops_per_token(cfg)
               + index_score_flops(cfg, causal)
               + 2 * index_score_flops(cfg, kept) + target_flops(cfg, kept))
    return (cfg["layers"] * (3.0 * trunk + indexer)
            + 3.0 * 2.0 * h * cfg["vocab"])


def index_train_flops_bytes(cfg: dict, batch: int, seq: int,
                            dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the indexer's products of one train step need,
    all layers: the scores forward over the causal pairs, their two
    gradients over the selected pairs, the target's one product over the
    selected pairs. Bytes: forward reads qI, kI, w and writes a float32
    score a causal pair; backward reads qI, kI, w and a float32 cotangent a
    selected pair and writes dqI, dkI, dw; the target reads q, k at their
    head counts, a float32 logsumexp a query head and a byte of selection a
    causal pair, and writes a float32 a selected pair."""
    sa = cfg["sa_config"]
    ih, ihd = sa["indexer_num_heads"], sa["indexer_head_dim"]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    kept, causal = selected_pairs(cfg, seq), causal_pairs(seq)
    flops = (index_score_flops(cfg, causal) + 2 * index_score_flops(cfg, kept)
             + target_flops(cfg, kept))
    operands = seq * (ih * ihd + ihd) * dtype_bytes + seq * ih * 4
    nbytes = (operands + 4 * causal                     # scores forward
              + 2 * operands + 4 * kept                 # their gradients
              + seq * (nh + nkv) * hd * dtype_bytes + seq * nh * 4
              + causal + 4 * kept)                      # the target
    return cfg["layers"] * batch * flops, cfg["layers"] * batch * nbytes


def attend_train_flops_bytes(cfg: dict, batch: int, seq: int,
                             dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the attention of one train step needs, all
    layers: six products over the SELECTED pairs (S = QK^T, O = PV, dV =
    P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q) at the query heads' count.
    Bytes: q, o, q, o, dO, dq at the query heads' count and k, v, k, v, dk,
    dv at the KV heads', and the selection's byte a causal pair read
    forward and backward."""
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    flops = 6 * 2.0 * nh * hd * selected_pairs(cfg, seq)
    nbytes = seq * hd * dtype_bytes * 6 * (nh + nkv) + 2 * causal_pairs(seq)
    return cfg["layers"] * batch * flops, cfg["layers"] * batch * nbytes
