"""Operations and bytes a step of a hybrid decoder LM NEEDS whose layers
attend by the gated delta rule (KDA) or by latent attention and feed
forward through a SwiGLU or through group-limited routed experts with a
shared one, as functions of shapes (`counts.py` has the rules: what the
mathematics requires, nothing an implementation adds). The delta rule is
counted as the RECURRENCE: a decay, a read, a rank-one update and a
read-out a token, not the matmuls and the solve its chunked form spends;
the experts at the assignments that really fell here.

Keys are the configuration file's: the published `config.json` names, with
`layers` the depth run from published layer `first_layer`, `vocab` the
vocabulary rows held, `num_experts` the experts held of `experts_total`,
`num_attention_heads` the heads held of `heads_total`.
"""
from __future__ import annotations

KDA, LATENT = "kda", "latent"


def layer_kinds(cfg: dict) -> list:
    """(attention kind, has experts) of every layer run."""
    return [(LATENT if (n + 1) % cfg["layer_group_size"] == 0 else KDA,
             n >= cfg["first_k_dense_replace"])
            for n in range(cfg["first_layer"],
                           cfg["first_layer"] + cfg["layers"])]


def count(cfg: dict, kind: str) -> int:
    return sum(k == kind for k, _ in layer_kinds(cfg))


def expert_layers(cfg: dict) -> int:
    return sum(sparse for _, sparse in layer_kinds(cfg))


def kda_scan_flops_per_token(cfg: dict) -> int:
    """The recurrence of one token, forward: per head the state [128, 128]
    times its decay (1), the read k^T S (a multiply and an add), the
    rank-one update with beta k (v - k^T S) (a multiply and an add) and the
    read-out S^T q (a multiply and an add): 7 operations a state element."""
    return 7 * cfg["num_attention_heads"] * cfg["head_dim"] ** 2


def kda_proj_flops_per_token(cfg: dict) -> int:
    """q, k, v and the decay's projection to heads x 128, beta and the gate
    to a scalar a head, the three depthwise convs, the output projection."""
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    width = nh * cfg["head_dim"]
    return 2 * (4 * d * width + 2 * d * nh
                + 3 * cfg["short_conv_kernel_size"] * width + width * d)


def mla_proj_flops_per_token(cfg: dict) -> int:
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    lora, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    kvb = cfg["qk_nope_head_dim"] + cfg["v_head_dim"]
    return 2 * (d * nh * qk + d * (lora + rope) + lora * nh * kvb + d * nh
                + nh * cfg["v_head_dim"] * d)


def attend_pairs(seq: int) -> int:
    """(query, key) pairs of one head over one causal row."""
    return seq * (seq + 1) // 2


def mla_attend_flops_per_token(cfg: dict, seq: int) -> float:
    """QK^T over `qk` features and PV over `v_head_dim` over the keys a
    query sees, on average over the row."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (2.0 * cfg["num_attention_heads"] * (qk + cfg["v_head_dim"])
            * attend_pairs(seq) / seq)


def gated_ffn_flops_per_token(hidden: int, width: int) -> int:
    return 2 * 3 * hidden * width


def layer_forward_flops_per_token(cfg: dict, seq: int, kind: str,
                                  sparse: bool,
                                  local_assign_per_tok: float) -> float:
    """One token's forward through one layer: its attention of `kind` and
    its feed-forward part (`sparse`: router, shared expert and the routed
    experts at `local_assign_per_tok` assignments a token that really fell
    on a held expert)."""
    d = cfg["hidden_size"]
    if kind == KDA:
        attn = kda_proj_flops_per_token(cfg) + kda_scan_flops_per_token(cfg)
    elif kind == LATENT:
        attn = mla_proj_flops_per_token(cfg) + mla_attend_flops_per_token(
            cfg, seq)
    else:
        raise ValueError(f"no attention kind {kind!r}")
    if not sparse:
        return attn + gated_ffn_flops_per_token(d, cfg["intermediate_size"])
    return (attn + 2 * d * cfg["experts_total"]
            + gated_ffn_flops_per_token(
                d, cfg["moe_shared_expert_intermediate_size"])
            + local_assign_per_tok * gated_ffn_flops_per_token(
                d, cfg["moe_intermediate_size"]))


def lm_forward_flops_per_token(cfg: dict, seq: int,
                               local_assign_per_tok: float) -> float:
    """One token's forward: every layer run and the head over the
    vocabulary held. The embedding is a read; norms, gates and activations
    are not counted."""
    return sum(layer_forward_flops_per_token(cfg, seq, kind, sparse,
                                             local_assign_per_tok)
               for kind, sparse in layer_kinds(cfg)) + 2.0 * cfg[
                   "hidden_size"] * cfg["vocab"]


def lm_train_flops_per_token(cfg: dict, seq: int,
                             local_assign_per_tok: float) -> float:
    """Forward + backward (twice the forward: a gradient for each operand
    of each product)."""
    return 3.0 * lm_forward_flops_per_token(cfg, seq, local_assign_per_tok)


def kda_scan_train_flops_bytes(cfg: dict, batch: int, seq: int,
                               dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the delta rules of one train step need, all KDA
    layers, as the recurrence: the state lives on the chip while a row goes
    by. Forward reads q, k, v [H, 128], the log decay g [H, 128] (float32)
    and beta [H] (float32) a token and writes o; backward reads them and do
    again and writes dq, dk, dv, dg, dbeta. Three times the forward's
    operations trained."""
    nh, hd = cfg["num_attention_heads"], cfg["head_dim"]
    tokens = batch * seq
    flops = count(cfg, KDA) * 3.0 * kda_scan_flops_per_token(cfg) * tokens
    per_token = (dtype_bytes * (4 + 7) * nh * hd + 4 * (1 + 2) * nh * hd
                 + 4 * (1 + 2) * nh)
    return flops, count(cfg, KDA) * tokens * per_token


def mla_flash_train_flops_bytes(cfg: dict, batch: int, seq: int,
                                dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the attention of one train step needs in the
    latent layers, over the causal pairs at the held heads: forward QK^T
    over `qk` and PV over `v` (2 matmuls), backward dV and dP over `v`, dQ
    and dK over `qk` (4); recomputing P in the backward kernels is the
    implementation's and not counted. Bytes: forward reads Q, K (`qk`
    wide), V and writes O (`v` wide); backward reads Q, K, V, O, dO and
    writes dQ, dK, dV."""
    nh = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    vd = cfg["v_head_dim"]
    n = count(cfg, LATENT)
    flops = n * 3 * 2.0 * batch * nh * attend_pairs(seq) * (qk + vd)
    nbytes = n * batch * nh * seq * dtype_bytes * (6 * qk + 6 * vd)
    return flops, nbytes


def moe_experts_train_flops_bytes(cfg: dict, assignments: float,
                                  dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the grouped matmuls of one train step need for
    `assignments` (token, slot) choices an expert layer on the held
    experts. Forward gate, up, down (3 matmuls an assignment), backward a
    gradient for each operand of each (6). Bytes: the held experts' weights
    read forward and backward and their gradients written; per assignment
    the rows in and out of each matmul."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    layers = expert_layers(cfg)
    flops = layers * 9 * 2.0 * assignments * d * f
    weights = cfg["num_experts"] * 3 * d * f * dtype_bytes
    rows = assignments * (2 * d + 3 * f) * dtype_bytes
    return flops, layers * (3 * weights + 3 * rows)
