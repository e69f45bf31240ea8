"""Operations and bytes a step of a hybrid decoder LM NEEDS whose routed
experts live in a latent: z = x W_a in front of the dispatch, experts of the
form W_down relu(W_up z)^2 at the latent's width, (their sum) W_b behind the
combine, the router and a shared expert on the full width (`counts.py` has
the rules: what the mathematics requires, nothing an implementation adds).
The state-space and attention layers are `counts_hybrid_ssm.py`'s, which
reads the same keys; the experts are counted at the assignments that really
fell here, never at the row buffer's size.

Keys are the configuration file's: the published `config.json` names whose
top-level numbers say what is HELD here (`mamba_num_heads` heads in
`n_groups` groups, `num_attention_heads` query heads on
`num_key_value_heads` KV heads, `n_routed_experts` of `experts_total`),
`layers` the depth run, `vocab` the vocabulary rows held.
"""
from __future__ import annotations

from . import counts_hybrid_ssm
from .counts_hybrid_ssm import (EXPERTS, layer_kinds,
                                relu2_ffn_flops_per_token)


def latent_proj_flops_per_token(cfg: dict) -> int:
    """W_a [d, latent] and W_b [latent, d]: two matrices a token."""
    return 2 * 2 * cfg["hidden_size"] * cfg["moe_latent_size"]


def expert_layer_forward_flops_per_token(cfg: dict,
                                         local_assign_per_tok: float) -> float:
    """One token's forward through one expert layer: the router over all
    the experts and the shared expert on the full width, the two latent
    projections, and the routed experts at the latent's width for the
    assignments that really fell on a held expert."""
    d = cfg["hidden_size"]
    return (2 * d * cfg["experts_total"]
            + relu2_ffn_flops_per_token(
                d, cfg["moe_shared_expert_intermediate_size"])
            + latent_proj_flops_per_token(cfg)
            + local_assign_per_tok * relu2_ffn_flops_per_token(
                cfg["moe_latent_size"], cfg["moe_intermediate_size"]))


def lm_forward_flops_per_token(cfg: dict, seq: int,
                               local_assign_per_tok: float) -> float:
    """One token's forward: every layer by its kind and the head over the
    vocabulary held. The embedding is a read; norms, gates and activations
    are not counted."""
    def one(kind):
        if kind == EXPERTS:
            return expert_layer_forward_flops_per_token(cfg,
                                                        local_assign_per_tok)
        return counts_hybrid_ssm.kind_forward_flops_per_token(
            cfg, seq, kind, local_assign_per_tok)

    return sum(map(one, layer_kinds(cfg))) + 2.0 * cfg["hidden_size"] * cfg[
        "vocab"]


def lm_train_flops_per_token(cfg: dict, seq: int,
                             local_assign_per_tok: float) -> float:
    """Forward + backward (twice the forward: a gradient for each operand
    of each product). Recomputation is the implementation's and not
    counted."""
    return 3.0 * lm_forward_flops_per_token(cfg, seq, local_assign_per_tok)


def moe_experts_train_flops_bytes(cfg: dict, assignments: float,
                                  dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the grouped matmuls of one train step need for
    `assignments` (token, slot) choices an expert layer on the held
    experts, two matrices [latent, f] and [f, latent] an expert. Forward up,
    down (2 matmuls an assignment), backward a gradient for each operand of
    each (4). Bytes: the held experts' weights read forward and backward and
    their gradients written; per assignment the rows in and out of each
    matmul."""
    lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    layers = layer_kinds(cfg).count(EXPERTS)
    flops = layers * 6 * 2.0 * assignments * lat * f
    weights = cfg["n_routed_experts"] * 2 * lat * f * dtype_bytes
    rows = assignments * (2 * lat + 2 * f) * dtype_bytes
    return flops, layers * (3 * weights + 3 * rows)
