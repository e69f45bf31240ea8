"""Operations and bytes a step of a decoder LM with sliding-window and full
attention layers, grouped KV heads and sparse experts NEEDS, as functions
of shapes (`counts.py` has the rules: what the mathematics requires,
nothing an implementation adds). A sliding layer needs the pairs inside
its window and no others; K and V are read and dK and dV written at the KV
heads' count; the experts at the assignments that really fell here.

Keys are the configuration file's: the published `config.json` names, with
`layers` the depth run (the first `layers` entries of `layer_types`),
`vocab` the vocabulary rows held, `num_experts` the experts held of
`experts_total`.
"""
from __future__ import annotations

SLIDING = "sliding_attention"


def layer_kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][:cfg["layers"]])


def attend_pairs(seq: int, window=None) -> int:
    """(query, key) pairs of one head over one row: causal, query i sees
    keys 0..i, with a window the last `window` of them."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def kind_pairs(cfg: dict, seq: int, kind: str) -> int:
    return attend_pairs(seq, cfg["sliding_window"] if kind == SLIDING
                        else None)


def gqa_proj_flops_per_token(cfg: dict) -> int:
    """q and the output projection at the query heads, k and v at the KV
    heads."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * (h * nh * hd + 2 * h * nkv * hd + nh * hd * h)


def attend_flops_per_token(cfg: dict, seq: int, kind: str) -> float:
    """QK^T and PV over the pairs a query of this kind of layer sees, on
    average over the row."""
    return (4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
            * kind_pairs(cfg, seq, kind) / seq)


def gated_ffn_flops_per_token(hidden: int, width: int) -> int:
    return 2 * 3 * hidden * width


def lm_forward_flops_per_token(cfg: dict, seq: int,
                               local_assign_per_tok: float) -> float:
    """One token's forward: every layer's projections, its attention by
    kind, the router over all experts and the routed experts at
    `local_assign_per_tok` assignments a token that really fell on a held
    expert; the head over the vocabulary held. The embedding is a read."""
    h = cfg["hidden_size"]
    sparse = (2 * h * cfg["experts_total"] + local_assign_per_tok
              * gated_ffn_flops_per_token(h, cfg["moe_intermediate_size"]))
    return sum(gqa_proj_flops_per_token(cfg)
               + attend_flops_per_token(cfg, seq, kind) + sparse
               for kind in layer_kinds(cfg)) + 2.0 * h * cfg["vocab"]


def lm_train_flops_per_token(cfg: dict, seq: int,
                             local_assign_per_tok: float) -> float:
    """Forward + backward (twice the forward: a gradient for each operand
    of each matmul)."""
    return 3.0 * lm_forward_flops_per_token(cfg, seq, local_assign_per_tok)


def flash_train_flops_bytes(cfg: dict, batch: int, seq: int, kind: str,
                            dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the attention of one train step needs in the
    layers of `kind`. Forward S = QK^T, O = PV; backward dV = P^T dO, dP =
    dO V^T, dQ = dS K, dK = dS^T Q: 6 matmuls over the pairs the kind sees;
    recomputing P in the backward kernels is the implementation's and not
    counted. Bytes: forward reads Q, K, V and writes O; backward reads Q,
    K, V, O, dO and writes dQ, dK, dV: six tensors at the query heads' count
    (Q, O, Q, O, dO, dQ) and six at the KV heads' (K, V, K, V, dK, dV)."""
    n = layer_kinds(cfg).count(kind)
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    flops = n * 6 * 2.0 * batch * nh * kind_pairs(cfg, seq, kind) * hd
    nbytes = n * batch * seq * hd * dtype_bytes * 6 * (nh + nkv)
    return flops, nbytes


def moe_experts_train_flops_bytes(cfg: dict, assignments: float,
                                  dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the grouped matmuls of one train step need for
    `assignments` (token, slot) choices a layer on the held experts, every
    layer sparse. Forward gate, up, down (3 matmuls an assignment), backward
    a gradient for each operand of each (6). Bytes: the held experts'
    weights read forward and backward and their gradients written; per
    assignment the rows in and out of each matmul."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = cfg["layers"] * 9 * 2.0 * assignments * h * f
    weights = cfg["num_experts"] * 3 * h * f * dtype_bytes
    rows = assignments * (2 * h + 3 * f) * dtype_bytes
    return flops, cfg["layers"] * (3 * weights + 3 * rows)
