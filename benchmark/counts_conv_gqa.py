"""Operations and bytes a step of a decoder LM NEEDS whose layers mix along
the sequence by a gated short convolution or by attention on grouped KV
heads and feed forward through a SwiGLU or through routed experts, under a
tied head, as functions of shapes (`counts.py` has the rules: what the
mathematics requires, nothing an implementation adds). The mixer's gates and
convolution are counted as ONE pass over the projection forward and one
backward, whichever ops implement them; the experts at the assignments that
really fell here.

Keys are the configuration file's: the published `config.json` names, with
`layers` the depth run from published layer `first_layer`, `vocab` the
vocabulary rows held, `num_experts` the experts held of `experts_total`.
"""
from __future__ import annotations

CONV, FULL = "conv", "full_attention"


def layer_kinds(cfg: dict) -> list:
    """(mixer kind, has experts) of every layer run."""
    return [(cfg["layer_types"][n], n >= cfg["num_dense_layers"])
            for n in range(cfg["first_layer"],
                           cfg["first_layer"] + cfg["layers"])]


def count(cfg: dict, kind: str) -> int:
    return sum(k == kind for k, _ in layer_kinds(cfg))


def expert_layers(cfg: dict) -> int:
    return sum(sparse for _, sparse in layer_kinds(cfg))


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def conv_proj_flops_per_token(cfg: dict) -> int:
    """The projection into [B | C | u] and the one out."""
    h = cfg["hidden_size"]
    return 2 * (h * 3 * h + h * h)


def conv_mix_flops_per_token(cfg: dict) -> int:
    """Forward, a channel: the first gate's product, `conv_L_cache` taps
    multiplied and summed, the second gate's product."""
    return (2 * cfg["conv_L_cache"] + 1) * cfg["hidden_size"]


def conv_mix_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """HBM bytes of the gates and the convolution of one layer, forward and
    backward, a token: forward the projection [B | C | u] read and y
    written (4 arrays of `hidden_size`); backward the projection and dy
    read and the projection's gradient written (7): 22 x 2048 = 45,056 B at
    bf16. The taps are 3 x 2048 numbers a layer and not counted."""
    return 11 * cfg["hidden_size"] * dtype_bytes


def conv_mix_train_flops_bytes(cfg: dict, batch: int, seq: int,
                               dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the gates and convolutions of one train step need,
    all conv layers. Backward: the convolution's cotangent through the taps
    in reverse, both gates' two gradients each, the taps' own gradient: four
    times the forward's products and sums at most; the bytes bind."""
    tokens = count(cfg, CONV) * batch * seq
    return (5.0 * tokens * conv_mix_flops_per_token(cfg),
            tokens * conv_mix_bytes_per_token(cfg, dtype_bytes))


def gqa_proj_flops_per_token(cfg: dict) -> int:
    """q and the output projection at the query heads, k and v at the KV
    heads."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * (h * nh * hd + 2 * h * nkv * hd + nh * hd * h)


def attend_flops_per_token(cfg: dict, seq: int) -> float:
    """QK^T and PV over the causal pairs, on average over the row."""
    return (4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
            * causal_pairs(seq) / seq)


def gated_ffn_flops_per_token(hidden: int, width: int) -> int:
    return 2 * 3 * hidden * width


def lm_forward_flops_per_token(cfg: dict, seq: int,
                               local_assign_per_tok: float) -> float:
    """One token's forward: every layer's mixer by kind, its dense part or
    the router over all experts and the routed experts at
    `local_assign_per_tok` assignments a token that really fell on a held
    expert; the tied head over the vocabulary held. The embedding is a
    read."""
    h = cfg["hidden_size"]
    mixer = {CONV: conv_proj_flops_per_token(cfg)
             + conv_mix_flops_per_token(cfg),
             FULL: gqa_proj_flops_per_token(cfg)
             + attend_flops_per_token(cfg, seq)}
    dense = gated_ffn_flops_per_token(h, cfg["intermediate_size"])
    sparse = (2 * h * cfg["experts_total"] + local_assign_per_tok
              * gated_ffn_flops_per_token(h, cfg["moe_intermediate_size"]))
    return sum(mixer[kind] + (sparse if has_experts else dense)
               for kind, has_experts in layer_kinds(cfg)
               ) + 2.0 * h * cfg["vocab"]


def lm_train_flops_per_token(cfg: dict, seq: int,
                             local_assign_per_tok: float) -> float:
    """Forward + backward (twice the forward: a gradient for each operand
    of each matmul); nothing recomputed."""
    return 3.0 * lm_forward_flops_per_token(cfg, seq, local_assign_per_tok)


def flash_train_flops_bytes(cfg: dict, batch: int, seq: int,
                            dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the attention layers' products of one train step
    need: forward S = QK^T, O = PV; backward dV = P^T dO, dP = dO V^T, dQ =
    dS K, dK = dS^T Q: six over the causal pairs; recomputing P in the
    backward kernels is the implementation's. Bytes: six tensors at the
    query heads' count (Q, O, Q, O, dO, dQ) and six at the KV heads' (K, V,
    K, V, dK, dV)."""
    n = count(cfg, FULL)
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    flops = n * 6 * 2.0 * batch * nh * causal_pairs(seq) * hd
    nbytes = n * batch * seq * hd * dtype_bytes * 6 * (nh + nkv)
    return flops, nbytes


def moe_experts_train_flops_bytes(cfg: dict, assignments: float,
                                  dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the grouped matmuls of one train step need for
    `assignments` (token, slot) choices a layer on the held experts, in the
    layers that have experts. Forward gate, up, down (3 matmuls an
    assignment), backward a gradient for each operand of each (6). Bytes:
    the held experts' weights read forward and backward and their gradients
    written; per assignment the rows in and out of each matmul."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n = expert_layers(cfg)
    flops = n * 9 * 2.0 * assignments * h * f
    weights = cfg["num_experts"] * 3 * h * f * dtype_bytes
    rows = assignments * (2 * h + 3 * f) * dtype_bytes
    return flops, n * (3 * weights + 3 * rows)
