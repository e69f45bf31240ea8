"""Operations and bytes a step of a hybrid decoder LM NEEDS whose layers
attend by the gated delta rule in its original form (an unbounded decay from
a low-rank gate, an element-wise low-rank output gate) or by softmax on
grouped KV heads without rotary positions under an element-wise gate, every
layer feeding forward through routed experts beside a shared one, as
functions of shapes (`counts.py` has the rules: what the mathematics
requires, nothing an implementation adds). The delta rule is counted as the
RECURRENCE: a decay, a read, a rank-one update and a read-out a token, not
the level-by-level products and the solve its chunked form spends; the
experts at the assignments that really fell here.

Keys are the configuration file's: the published `config.json` names, with
`layers` the depth run from published layer `first_layer`, `vocab` the
vocabulary rows held, `n_routed_experts` the experts held of
`experts_total`, `num_attention_heads` / `num_key_value_heads` and
`linear_attn_config.num_heads` the heads held.
"""
from __future__ import annotations

KDA, GQA = "kda", "gqa"


def layer_kinds(cfg: dict) -> list:
    """The attention kind of every layer run; all of them have experts."""
    return [GQA if n in cfg["gqa_layers"] else KDA
            for n in range(cfg["first_layer"],
                           cfg["first_layer"] + cfg["layers"])]


def count(cfg: dict, kind: str) -> int:
    return layer_kinds(cfg).count(kind)


def kda_scan_flops_per_token(cfg: dict) -> int:
    """The recurrence of one token, forward: per head the state [128, 128]
    times its decay (1), the read k^T S (a multiply and an add), the
    rank-one update with beta k (v - k^T S) (a multiply and an add) and the
    read-out S^T q (a multiply and an add): 7 operations a state element."""
    lin = cfg["linear_attn_config"]
    return 7 * lin["num_heads"] * lin["head_dim"] ** 2


def kda_proj_flops_per_token(cfg: dict) -> int:
    """q, k, v to heads x 128, the decay's and the output gate's low-rank
    pairs (hidden -> 128 -> heads x 128), beta to a scalar a head, the three
    depthwise convs, the output projection."""
    d, lin = cfg["hidden_size"], cfg["linear_attn_config"]
    nh, hd = lin["num_heads"], lin["head_dim"]
    width = nh * hd
    return 2 * (3 * d * width + 2 * (d * hd + hd * width) + d * nh
                + 3 * lin["short_conv_kernel_size"] * width + width * d)


def gqa_proj_flops_per_token(cfg: dict) -> int:
    """q, the gate and the output projection at the query heads, k and v at
    the KV heads."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * (3 * d * nh * hd + 2 * d * nkv * hd)


def causal_pairs(seq: int) -> int:
    """(query, key) pairs of one head over one causal row."""
    return seq * (seq + 1) // 2


def attend_flops_per_token(cfg: dict, seq: int) -> float:
    """QK^T and PV over the causal pairs, on average over the row."""
    return (4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
            * causal_pairs(seq) / seq)


def gated_ffn_flops_per_token(hidden: int, width: int) -> int:
    return 2 * 3 * hidden * width


def lm_forward_flops_per_token(cfg: dict, seq: int,
                               local_assign_per_tok: float) -> float:
    """One token's forward: every layer's mixer by kind, the router over all
    experts, the shared expert and the routed experts at
    `local_assign_per_tok` assignments a token that really fell on a held
    expert; the head over the vocabulary held. The embedding is a read;
    norms, gates and activations are not counted."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    mixer = {KDA: kda_proj_flops_per_token(cfg)
             + kda_scan_flops_per_token(cfg),
             GQA: gqa_proj_flops_per_token(cfg)
             + attend_flops_per_token(cfg, seq)}
    sparse = (2 * d * cfg["experts_total"]
              + (cfg["n_shared_experts"] + local_assign_per_tok)
              * gated_ffn_flops_per_token(d, f))
    return sum(mixer[kind] + sparse for kind in layer_kinds(cfg)
               ) + 2.0 * d * cfg["vocab"]


def lm_train_flops_per_token(cfg: dict, seq: int,
                             local_assign_per_tok: float) -> float:
    """Forward + backward (twice the forward: a gradient for each operand
    of each product); nothing recomputed."""
    return 3.0 * lm_forward_flops_per_token(cfg, seq, local_assign_per_tok)


def kda_scan_train_flops_bytes(cfg: dict, batch: int, seq: int,
                               dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the delta rules of one train step need, all KDA
    layers, as the recurrence: the state lives on the chip while a row goes
    by. Forward reads q, k, v [H, 128], the log decay g [H, 128] (float32)
    and beta [H] (float32) a token and writes o; backward reads them and do
    again and writes dq, dk, dv, dg, dbeta. Three times the forward's
    operations trained. The form the kernels run for an unbounded decay
    (six levels of products a chunk where the bounded form has four blocks)
    is the implementation's: the count is the bounded sibling's
    (`counts_kda_mla.py`), so the two cells' shares read one yardstick."""
    lin = cfg["linear_attn_config"]
    nh, hd = lin["num_heads"], lin["head_dim"]
    tokens = batch * seq
    flops = count(cfg, KDA) * 3.0 * kda_scan_flops_per_token(cfg) * tokens
    per_token = (dtype_bytes * (4 + 7) * nh * hd + 4 * (1 + 2) * nh * hd
                 + 4 * (1 + 2) * nh)
    return flops, count(cfg, KDA) * tokens * per_token


def flash_train_flops_bytes(cfg: dict, batch: int, seq: int,
                            dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the softmax layers' products of one train step
    need: forward S = QK^T, O = PV; backward dV = P^T dO, dP = dO V^T, dQ =
    dS K, dK = dS^T Q: six over the causal pairs; recomputing P in the
    backward kernels is the implementation's. Bytes: six tensors at the
    query heads' count (Q, O, Q, O, dO, dQ) and six at the KV heads' (K, V,
    K, V, dK, dV)."""
    n = count(cfg, GQA)
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    flops = n * 6 * 2.0 * batch * nh * causal_pairs(seq) * hd
    nbytes = n * batch * seq * hd * dtype_bytes * 6 * (nh + nkv)
    return flops, nbytes


def moe_experts_train_flops_bytes(cfg: dict, assignments: float,
                                  dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the grouped matmuls of one train step need for
    `assignments` (token, slot) choices a layer on the held experts, every
    layer run. Forward gate, up, down (3 matmuls an assignment), backward a
    gradient for each operand of each (6). Bytes: the held experts' weights
    read forward and backward and their gradients written; per assignment
    the rows in and out of each matmul."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n = cfg["layers"]
    flops = n * 9 * 2.0 * assignments * d * f
    weights = cfg["n_routed_experts"] * 3 * d * f * dtype_bytes
    rows = assignments * (2 * d + 3 * f) * dtype_bytes
    return flops, n * (3 * weights + 3 * rows)
