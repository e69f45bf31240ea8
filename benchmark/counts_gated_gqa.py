"""Operations and bytes a step of a decoder LM NEEDS whose layers attend in a
sliding window or over the whole row with a query-head count that DIFFERS
BY LAYER on the same KV heads, under an element-wise output gate, and feed
forward densely (the leading layer) or through routed experts beside a
shared one, as functions of shapes (`counts.py` has the rules: what the
mathematics requires, nothing an implementation adds). A sliding layer
needs the pairs inside its window and no others; q, the gate and the output
projection are counted at THAT layer's head count, k and v at the KV heads';
the experts at the assignments that really fell here. Rotary turns, norms,
the gate's sigmoid and activations are not counted.

Keys are the configuration file's: the published `config.json` names
(`num_attention_heads_per_layer`, `layer_types`, `mlp_layer_types` by
published index), with `layers` the depth run from published layer
`first_layer`, `vocab` the vocabulary rows held, `num_experts` the experts
held of `experts_total`.
"""
from __future__ import annotations

from .counts_window_gqa import attend_pairs, gated_ffn_flops_per_token

SLIDING, FULL = "sliding_attention", "full_attention"


def layers_here(cfg: dict) -> range:
    return range(cfg["first_layer"], cfg["first_layer"] + cfg["layers"])


def layers_of(cfg: dict, kind: str) -> list:
    """The published indices of the layers run that are of `kind`."""
    return [n for n in layers_here(cfg) if cfg["layer_types"][n] == kind]


def sparse_layers(cfg: dict) -> list:
    return [n for n in layers_here(cfg)
            if cfg["mlp_layer_types"][n] == "sparse"]


def layer_pairs(cfg: dict, seq: int, n: int) -> int:
    """(query, key) pairs one head of layer n sees over one row."""
    return attend_pairs(seq, cfg["sliding_window"]
                        if cfg["layer_types"][n] == SLIDING else None)


def proj_flops_per_token(cfg: dict, n: int) -> int:
    """q, the gate and the output projection at layer n's query heads, k
    and v at the KV heads."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = (cfg["num_attention_heads_per_layer"][n],
               cfg["num_key_value_heads"])
    return 2 * (3 * d * nh * hd + 2 * d * nkv * hd)


def attend_flops_per_token(cfg: dict, seq: int, n: int) -> float:
    """QK^T and PV over the pairs a query of layer n sees, on average over
    the row, at that layer's head count."""
    return (4.0 * cfg["num_attention_heads_per_layer"][n] * cfg["head_dim"]
            * layer_pairs(cfg, seq, n) / seq)


def ffn_flops_per_token(cfg: dict, n: int,
                        local_assign_per_tok: float) -> float:
    """Layer n's feed-forward part: the dense SwiGLU, or the router over all
    experts, the shared expert on every token and the routed experts at
    `local_assign_per_tok` assignments a token."""
    d = cfg["hidden_size"]
    if cfg["mlp_layer_types"][n] == "dense":
        return gated_ffn_flops_per_token(d, cfg["intermediate_size"])
    return (2 * d * cfg["experts_total"]
            + gated_ffn_flops_per_token(
                d, cfg["shared_expert_intermediate_size"])
            + local_assign_per_tok * gated_ffn_flops_per_token(
                d, cfg["moe_intermediate_size"]))


def lm_forward_flops_per_token(cfg: dict, seq: int,
                               local_assign_per_tok: float) -> float:
    """One token's forward: every layer's projections and attention at its
    own head count and kind, its feed-forward part; the head over the
    vocabulary held. The embedding is a read."""
    return sum(proj_flops_per_token(cfg, n)
               + attend_flops_per_token(cfg, seq, n)
               + ffn_flops_per_token(cfg, n, local_assign_per_tok)
               for n in layers_here(cfg)) + 2.0 * cfg["hidden_size"] * cfg[
                   "vocab"]


def lm_train_flops_per_token(cfg: dict, seq: int,
                             local_assign_per_tok: float) -> float:
    """Forward + backward (twice the forward: a gradient for each operand
    of each matmul); nothing recomputed."""
    return 3.0 * lm_forward_flops_per_token(cfg, seq, local_assign_per_tok)


def flash_train_flops_bytes(cfg: dict, batch: int, seq: int, kind: str,
                            dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the attention of one train step needs in the
    layers of `kind`, each at its own head count. Forward S = QK^T, O = PV;
    backward dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q: 6 matmuls
    over the pairs the kind sees; recomputing P in the backward kernels is
    the implementation's and not counted, nor a forward run again under
    recomputation. Bytes: six tensors at the query heads' count (Q, O, Q,
    O, dO, dQ) and six at the KV heads' (K, V, K, V, dK, dV)."""
    hd, nkv = cfg["head_dim"], cfg["num_key_value_heads"]
    flops = nbytes = 0.0
    for n in layers_of(cfg, kind):
        nh = cfg["num_attention_heads_per_layer"][n]
        flops += 6 * 2.0 * batch * nh * layer_pairs(cfg, seq, n) * hd
        nbytes += batch * seq * hd * dtype_bytes * 6 * (nh + nkv)
    return flops, nbytes


def moe_experts_train_flops_bytes(cfg: dict, assignments: float,
                                  dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the grouped matmuls of one train step need for
    `assignments` (token, slot) choices a layer on the held experts, in
    every SPARSE layer run. Forward gate, up, down (3 matmuls an
    assignment), backward a gradient for each operand of each (6). Bytes:
    the held experts' weights read forward and backward and their
    gradients written; per assignment the rows in and out of each
    matmul."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n = len(sparse_layers(cfg))
    flops = n * 9 * 2.0 * assignments * d * f
    weights = cfg["num_experts"] * 3 * d * f * dtype_bytes
    rows = assignments * (2 * d + 3 * f) * dtype_bytes
    return flops, n * (3 * weights + 3 * rows)
