"""Operations and bytes a step of a hybrid decoder LM NEEDS, whose layers
are each a Mamba-2 state-space mixer, ungated relu^2 experts with a shared
one, or attention on grouped KV heads, as functions of shapes (`counts.py`
has the rules: what the mathematics requires, nothing an implementation
adds). The selective scan is counted as the RECURRENCE: a state update and
a read-out a token, not the matmuls its chunked form spends; the experts at
the assignments that really fell here.

Keys are the configuration file's: the published `config.json` names, with
`layers` the depth run (the first `layers` letters of
`hybrid_override_pattern`: `M`, `E` or `*`), `vocab` the vocabulary rows
held, `n_routed_experts` the experts held of `experts_total`.
"""
from __future__ import annotations

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def layer_kinds(cfg: dict) -> str:
    return cfg["hybrid_override_pattern"][:cfg["layers"]]


def ssm_dims(cfg: dict) -> tuple:
    """(H, P, G, N): heads, head width, groups of B and C, state width."""
    return (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
            cfg["ssm_state_size"])


def ssm_scan_flops_per_token(cfg: dict) -> int:
    """The recurrence of one token, forward: per head the state [P, N]
    times its decay, plus dt x (x) B (a multiply and an add), and the
    read-out h C (a multiply and an add): 5 operations a state element."""
    h, p, _, n = ssm_dims(cfg)
    return 5 * h * p * n


def ssm_proj_flops_per_token(cfg: dict) -> int:
    """The input projection to [z | x | B | C | dt], the depthwise conv
    over x, B, C, and the output projection."""
    h, p, g, n = ssm_dims(cfg)
    d, d_in, xbc = cfg["hidden_size"], h * p, h * p + 2 * g * n
    return 2 * (d * (d_in + xbc + h) + cfg["conv_kernel"] * xbc + d_in * d)


def relu2_ffn_flops_per_token(hidden: int, width: int) -> int:
    """W_down relu(W_up x)^2: two matrices."""
    return 2 * 2 * hidden * width


def attn_proj_flops_per_token(cfg: dict) -> int:
    """q and the output projection at the query heads, k and v at the KV
    heads."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * (d * nh * hd + 2 * d * nkv * hd + nh * hd * d)


def attend_pairs(seq: int) -> int:
    """(query, key) pairs of one head over one causal row."""
    return seq * (seq + 1) // 2


def attend_flops_per_token(cfg: dict, seq: int) -> float:
    """QK^T and PV over the keys a query sees, on average over the row."""
    return (4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
            * attend_pairs(seq) / seq)


def kind_forward_flops_per_token(cfg: dict, seq: int, kind: str,
                                 local_assign_per_tok: float) -> float:
    """One token's forward through one layer of `kind`."""
    d = cfg["hidden_size"]
    if kind == MAMBA:
        return ssm_proj_flops_per_token(cfg) + ssm_scan_flops_per_token(cfg)
    if kind == ATTENTION:
        return attn_proj_flops_per_token(cfg) + attend_flops_per_token(cfg,
                                                                       seq)
    if kind != EXPERTS:
        raise ValueError(f"hybrid_override_pattern: no kind {kind!r}")
    return (2 * d * cfg["experts_total"]
            + relu2_ffn_flops_per_token(
                d, cfg["moe_shared_expert_intermediate_size"])
            + local_assign_per_tok * relu2_ffn_flops_per_token(
                d, cfg["moe_intermediate_size"]))


def lm_forward_flops_per_token(cfg: dict, seq: int,
                               local_assign_per_tok: float) -> float:
    """One token's forward: every layer by its kind (the routed experts at
    `local_assign_per_tok` assignments a token that really fell on a held
    expert) and the head over the vocabulary held. The embedding is a
    read; norms, gates and activations are not counted."""
    return sum(kind_forward_flops_per_token(cfg, seq, kind,
                                            local_assign_per_tok)
               for kind in layer_kinds(cfg)) + 2.0 * cfg[
                   "hidden_size"] * cfg["vocab"]


def lm_train_flops_per_token(cfg: dict, seq: int,
                             local_assign_per_tok: float) -> float:
    """Forward + backward (twice the forward: a gradient for each operand
    of each product)."""
    return 3.0 * lm_forward_flops_per_token(cfg, seq, local_assign_per_tok)


def ssm_scan_train_flops_bytes(cfg: dict, batch: int, seq: int,
                               dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the selective scans of one train step need, all
    state-space layers, as the recurrence: the state lives on the chip
    while a row goes by. Forward reads x [H, P], B, C [G, N] and dt [H]
    (float32) a token and writes y; backward reads them and dy again and
    writes dx, dB, dC, ddt. Three times the forward's operations trained."""
    h, p, g, n = ssm_dims(cfg)
    layers = layer_kinds(cfg).count(MAMBA)
    tokens = batch * seq
    flops = layers * 3.0 * ssm_scan_flops_per_token(cfg) * tokens
    per_token = (dtype_bytes * ((2 + 3) * h * p + (2 + 4) * g * n)
                 + 4 * (1 + 2) * h)
    return flops, layers * tokens * per_token


def flash_train_flops_bytes(cfg: dict, batch: int, seq: int,
                            dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the attention of one train step needs in the
    attention layers: 6 matmuls over the causal pairs at the query heads'
    count; six tensors at the query heads' count (Q, O, Q, O, dO, dQ) and
    six at the KV heads' (K, V, K, V, dK, dV). Recomputing P in the backward
    kernels is the implementation's and not counted."""
    n = layer_kinds(cfg).count(ATTENTION)
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    flops = n * 6 * 2.0 * batch * nh * attend_pairs(seq) * hd
    nbytes = n * batch * seq * hd * dtype_bytes * 6 * (nh + nkv)
    return flops, nbytes


def moe_experts_train_flops_bytes(cfg: dict, assignments: float,
                                  dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the grouped matmuls of one train step need for
    `assignments` (token, slot) choices an expert layer on the held
    experts. Forward up, down (2 matmuls an assignment), backward a
    gradient for each operand of each (4). Bytes: the held experts' weights
    read forward and backward and their gradients written; per assignment
    the rows in and out of each matmul."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    layers = layer_kinds(cfg).count(EXPERTS)
    flops = layers * 6 * 2.0 * assignments * d * f
    weights = cfg["n_routed_experts"] * 2 * d * f * dtype_bytes
    rows = assignments * (2 * d + 2 * f) * dtype_bytes
    return flops, layers * (3 * weights + 3 * rows)
