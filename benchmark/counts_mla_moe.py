"""Operations and bytes a step of a latent-attention, sparse-expert LM
NEEDS, as functions of shapes (`counts.py` has the rules: what the
mathematics requires, nothing an implementation adds).

Keys are the configuration file's: the published `config.json` names, with
`layers` the depth run, `vocab` the vocabulary rows held, `n_routed_experts`
the experts held of `experts_total`.
"""
from __future__ import annotations


def mla_proj_flops_per_token(cfg: dict) -> int:
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    lora, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    kvb = cfg["qk_nope_head_dim"] + cfg["v_head_dim"]
    return 2 * (h * nh * qk + h * (lora + rope) + lora * nh * kvb
                + nh * cfg["v_head_dim"] * h)


def mla_attend_flops_per_token(cfg: dict, seq: int) -> float:
    """QK^T over `qk` features and PV over `v_head_dim`, causal: a query
    sees half the keys on average."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 2.0 * seq * cfg["num_attention_heads"] * (
        qk + cfg["v_head_dim"]) / 2


def gated_ffn_flops_per_token(hidden: int, width: int) -> int:
    return 2 * 3 * hidden * width


def lm_forward_flops_per_token(cfg: dict, seq: int,
                               local_assign_per_tok: float) -> float:
    """One token's forward: every layer's projections and causal attention;
    the dense FFN of the leading layers; router, shared expert and the
    routed experts at `local_assign_per_tok` assignments a token that
    really fell on a held expert; the head over the vocabulary held.
    The embedding is a read."""
    h = cfg["hidden_size"]
    attn = mla_proj_flops_per_token(cfg) + mla_attend_flops_per_token(cfg,
                                                                      seq)
    dense = gated_ffn_flops_per_token(h, cfg["intermediate_size"])
    f = cfg["moe_intermediate_size"]
    sparse = (2 * h * cfg["experts_total"]
              + gated_ffn_flops_per_token(h, f * cfg["n_shared_experts"])
              + local_assign_per_tok * gated_ffn_flops_per_token(h, f))
    n_dense = min(cfg["first_k_dense_replace"], cfg["layers"])
    return (cfg["layers"] * attn + n_dense * dense
            + (cfg["layers"] - n_dense) * sparse + 2.0 * h * cfg["vocab"])


def lm_train_flops_per_token(cfg: dict, seq: int,
                             local_assign_per_tok: float) -> float:
    """Forward + backward (twice the forward: a gradient for each operand
    of each matmul)."""
    return 3.0 * lm_forward_flops_per_token(cfg, seq, local_assign_per_tok)


def mla_flash_train_flops_bytes(cfg: dict, batch: int, seq: int,
                                dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the attention of one train step needs, all
    layers, causal. Forward S = QK^T over `qk`, O = PV over `v` (2
    matmuls); backward dV = P^T dO and dP = dO V^T over `v`, dQ = dS K and
    dK = dS^T Q over `qk` (4 matmuls); recomputing P in the backward
    kernels is the implementation's and not counted. Bytes: forward reads
    Q, K (`qk` wide), V and writes O (`v` wide); backward reads Q, K, V, O,
    dO and writes dQ, dK, dV."""
    nh = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    vd = cfg["v_head_dim"]
    pair = 2.0 * batch * nh * seq * seq / 2       # one causal matmul a feature
    flops = cfg["layers"] * 3 * pair * (qk + vd)
    rows = batch * nh * seq * dtype_bytes
    nbytes = cfg["layers"] * rows * ((2 * qk + 2 * vd)
                                     + (4 * qk + 4 * vd))
    return flops, nbytes


def moe_experts_train_flops_bytes(cfg: dict, assignments: float,
                                  expert_layers: int,
                                  dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the grouped matmuls of one train step need for
    `assignments` (token, slot) choices a layer on the held experts.
    Forward gate, up, down (3 matmuls an assignment), backward a gradient
    for each operand of each (6); the gate and up matmuls run again in the
    backward by the implementation's choice, not counted. Bytes: the held
    experts' weights read forward and backward and their gradients
    written; per assignment the rows in and out of each matmul."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = expert_layers * 9 * 2.0 * assignments * h * f
    weights = cfg["n_routed_experts"] * 3 * h * f * dtype_bytes
    rows = assignments * (2 * h + 3 * f) * dtype_bytes
    return flops, expert_layers * (3 * weights + 3 * rows)
