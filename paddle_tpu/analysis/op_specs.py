"""Op slot/attr metadata for the program verifier.

The reference declares every op's slots and attrs up front (OpProto /
OpMaker, `op_registry.h`) and validates op descs against them; this
runtime's registry holds only lowerings (ops/registry.py), so slot names
and attrs were historically checked by nothing until trace time. This
module attaches OpSpec metadata to the registry (`registry.set_spec`) for
the ops the pass pipeline emits or rewrites plus the high-traffic core —
coverage is deliberately incremental: an op without a spec still gets the
structural checks (def-before-use, dangling inputs, dtype rules), just not
slot/attr validation. Add a spec here whenever the verifier's lint sweep
surfaces an op whose malformed desc slipped through to a trace-time error.

Spec semantics (validated by analysis/verifier.py):

* inputs/outputs: {slot: (min_arity, max_arity|None)}; min >= 1 makes the
  slot required. Slots not listed are "unknown_slot" errors unless
  allow_extra_slots.
* required_attrs: missing -> "missing_attr" error.
* attr_types: {name: type | (types,)}; a present attr of the wrong type is
  an "attr_type" error. list/tuple are interchangeable.
* closed_attrs: attrs outside attr_types/required_attrs/COMMON_ATTRS are
  "unknown_attr" warnings (only sensible for ops this repo fully emits —
  the __dunder__ structural ops).
* sharding: the op's spec-propagation rule name (analysis/sharding.py
  RULES) — the static analog of the reference auto_parallel completion
  rules (elementwise-follows-input, matmul contraction, ...). Ops without
  a rule propagate replicated outputs and draw an "unknown_sharding_rule"
  warning from the sharding lint.
* cross_batch: the op couples examples ACROSS the global batch beyond a
  trailing mean-reduced loss (sync-BN semantics, MoE FCFS capacity /
  routing stats) — the manual-dp shard_map path must decline such
  programs. THE one table: parallel/zero.py's runtime decline and the
  build-time sharding lint both read it via `cross_batch_ops()`.
"""
from __future__ import annotations

from typing import Dict, FrozenSet, Optional, Tuple

from ..ops import registry

# Attrs any op may carry: role/bookkeeping markers set by builders and
# program transforms, never consumed by a specific lowering.
COMMON_ATTRS = frozenset({
    "op_role", "__rng_seed__", "pipeline_stage", "is_test", "auto_selected",
    "name_scope",
})


class OpSpec:
    __slots__ = ("inputs", "outputs", "required_attrs", "attr_types",
                 "closed_attrs", "allow_extra_slots", "sharding",
                 "cross_batch")

    def __init__(self, inputs: Optional[Dict[str, Tuple]] = None,
                 outputs: Optional[Dict[str, Tuple]] = None,
                 required_attrs=(), attr_types: Optional[dict] = None,
                 closed_attrs: bool = False, allow_extra_slots: bool = False,
                 sharding: Optional[str] = None, cross_batch: bool = False):
        self.inputs = dict(inputs or {})
        self.outputs = dict(outputs or {})
        self.required_attrs = tuple(required_attrs)
        self.attr_types = dict(attr_types or {})
        self.closed_attrs = closed_attrs
        self.allow_extra_slots = allow_extra_slots
        self.sharding = sharding
        self.cross_batch = cross_batch


_LIST = (list, tuple)
_NUM = (int, float)

# one required entry; "many" slots take 1..N; (0, ...) slots are optional
ONE = (1, 1)
MANY = (1, None)
OPT = (0, 1)
ANY = (0, None)

SPECS: Dict[str, OpSpec] = {
    # --- pass-pipeline structural ops (fully owned by this repo) ---------
    "__segment__": OpSpec(
        inputs={"X": ANY}, outputs={"Out": MANY},
        required_attrs=("sub_ops", "in_names", "out_names"),
        attr_types={"sub_ops": _LIST, "in_names": _LIST, "out_names": _LIST,
                    "remat": bool},
        closed_attrs=True),
    "__layer_scan__": OpSpec(
        inputs={"X": ONE, "Inv": ANY, "Stacked": ANY},
        outputs={"Out": ONE},
        required_attrs=("sub_ops", "num_layers", "carry_in", "carry_out",
                        "inv_names", "stacked_names", "layer_seeds"),
        attr_types={"sub_ops": _LIST, "num_layers": int, "carry_in": str,
                    "carry_out": str, "inv_names": _LIST,
                    "stacked_names": _LIST, "layer_seeds": _LIST,
                    "remat": bool, "zero3_flat": _LIST},
        closed_attrs=True),
    "__bucket_sync__": OpSpec(
        inputs={"X": MANY}, outputs={"Out": MANY},
        required_attrs=("sizes", "shapes", "dtype"),
        attr_types={"sizes": _LIST, "shapes": _LIST, "dtype": str},
        closed_attrs=True),
    "__zero_update__": OpSpec(
        inputs={"Grad": MANY, "LearningRate": ONE, "FlatState": ANY,
                "Param": ANY, "FlatParam": OPT,
                "Beta1Pow": OPT, "Beta2Pow": OPT},
        outputs={"ParamOut": ANY, "FlatStateOut": ANY, "FlatParamOut": OPT,
                 "FlatGradOut": OPT},
        required_attrs=("update_op", "update_attrs", "sizes", "shapes",
                        "padded", "dtype", "state_kinds", "stage", "layout"),
        attr_types={"update_op": str, "update_attrs": dict, "sizes": _LIST,
                    "shapes": _LIST, "padded": int, "dtype": str,
                    "state_kinds": _LIST, "stage": int, "layout": str,
                    "pre_synced": bool, "num_layers": int},
        closed_attrs=True),
    "__zero_gather__": OpSpec(
        inputs={"FlatParam": ONE}, outputs={"Out": MANY},
        required_attrs=("sizes", "shapes", "dtypes", "padded"),
        attr_types={"sizes": _LIST, "shapes": _LIST, "dtypes": _LIST,
                    "padded": int},
        closed_attrs=True),
    "__zero_pack__": OpSpec(
        inputs={"X": MANY}, outputs={"Out": ONE},
        required_attrs=("padded", "dtype"),
        attr_types={"padded": int, "dtype": str, "sizes": _LIST,
                    "layout": str},
        closed_attrs=True),
    # --- control flow ----------------------------------------------------
    "__cond__": OpSpec(
        inputs={"Cond": ONE, "Free": ANY}, outputs={"Out": MANY},
        required_attrs=("true_block", "false_block", "true_outs",
                        "false_outs", "free_names"),
        attr_types={"true_block": int, "false_block": int,
                    "true_outs": _LIST, "false_outs": _LIST,
                    "free_names": _LIST},
        closed_attrs=True),
    "__while__": OpSpec(
        inputs={"Cond": ONE, "Carried": MANY, "Free": ANY},
        outputs={"Out": MANY},
        required_attrs=("sub_block", "carried_names", "free_names",
                        "cond_name"),
        attr_types={"sub_block": int, "carried_names": _LIST,
                    "free_names": _LIST, "cond_name": str,
                    "trip_bound": int},
        closed_attrs=True),
    "__scan__": OpSpec(
        inputs={"X": ANY, "Init": ANY, "Free": ANY}, outputs={"Out": MANY},
        required_attrs=("sub_block", "x_names", "mem_pre_names",
                        "mem_upd_names", "out_names", "free_names"),
        attr_types={"sub_block": int},
        closed_attrs=True),
    # --- optimizer update ops (the ZeRO pass rewrites these) -------------
    "sgd": OpSpec(
        inputs={"Param": ONE, "Grad": ONE, "LearningRate": ONE},
        outputs={"ParamOut": ONE}, sharding="param_update"),
    "momentum": OpSpec(
        inputs={"Param": ONE, "Grad": ONE, "Velocity": ONE,
                "LearningRate": ONE},
        outputs={"ParamOut": ONE, "VelocityOut": ONE},
        attr_types={"mu": _NUM, "use_nesterov": bool},
        sharding="param_update"),
    "adam": OpSpec(
        inputs={"Param": ONE, "Grad": ONE, "LearningRate": ONE,
                "Moment1": ONE, "Moment2": ONE, "Beta1Pow": ONE,
                "Beta2Pow": ONE},
        outputs={"ParamOut": ONE, "Moment1Out": ONE, "Moment2Out": ONE,
                 "Beta1PowOut": OPT, "Beta2PowOut": OPT},
        attr_types={"beta1": _NUM, "beta2": _NUM, "epsilon": _NUM},
        sharding="param_update"),
    "adamw": OpSpec(
        inputs={"Param": ONE, "Grad": ONE, "LearningRate": ONE,
                "Moment1": ONE, "Moment2": ONE, "Beta1Pow": ONE,
                "Beta2Pow": ONE},
        outputs={"ParamOut": ONE, "Moment1Out": ONE, "Moment2Out": ONE,
                 "Beta1PowOut": OPT, "Beta2PowOut": OPT},
        attr_types={"beta1": _NUM, "beta2": _NUM, "epsilon": _NUM,
                    "coeff": _NUM, "weight_decay": _NUM},
        sharding="param_update"),
    # --- high-traffic core ops -------------------------------------------
    "sum": OpSpec(inputs={"X": MANY}, outputs={"Out": ONE},
                  sharding="elementwise"),
    "assign": OpSpec(inputs={"X": ONE}, outputs={"Out": ONE},
                     sharding="follow_x"),
    "cast": OpSpec(inputs={"X": ONE}, outputs={"Out": ONE},
                   attr_types={"out_dtype": str, "in_dtype": str},
                   sharding="follow_x"),
    "fill_constant": OpSpec(
        inputs={}, outputs={"Out": ONE},
        attr_types={"shape": _LIST, "dtype": str, "value": _NUM},
        sharding="replicated"),
    "concat": OpSpec(inputs={"X": MANY}, outputs={"Out": ONE},
                     attr_types={"axis": int}, sharding="concat"),
    "stack": OpSpec(inputs={"X": MANY}, outputs={"Y": ONE},
                    attr_types={"axis": int}, sharding="stack"),
    "where": OpSpec(inputs={"Condition": ONE, "X": ONE, "Y": ONE},
                    outputs={"Out": ONE}, sharding="elementwise"),
    "scale": OpSpec(inputs={"X": ONE}, outputs={"Out": ONE},
                    attr_types={"scale": _NUM, "bias": _NUM,
                                "bias_after_scale": bool},
                    sharding="follow_x"),
    "mean": OpSpec(inputs={"X": ONE}, outputs={"Out": ONE},
                   sharding="reduce_all"),
    "matmul": OpSpec(inputs={"X": ONE, "Y": ONE}, outputs={"Out": ONE},
                     attr_types={"transpose_X": bool, "transpose_Y": bool,
                                 "alpha": _NUM},
                     sharding="matmul"),
    "mul": OpSpec(inputs={"X": ONE, "Y": ONE}, outputs={"Out": ONE},
                  attr_types={"x_num_col_dims": int, "y_num_col_dims": int},
                  sharding="matmul"),
    "dropout": OpSpec(
        inputs={"X": ONE}, outputs={"Out": ONE, "Mask": OPT},
        attr_types={"dropout_prob": _NUM, "dropout_implementation": str,
                    "seed": int, "fix_seed": bool},
        sharding="follow_x"),
    "softmax_with_cross_entropy": OpSpec(
        inputs={"Logits": ONE, "Label": ONE},
        outputs={"Softmax": OPT, "Loss": ONE},
        attr_types={"soft_label": bool, "ignore_index": int, "axis": int},
        sharding="softmax_ce"),
    # --- zoo coverage: every op the 11-program lint zoo emits ------------
    # (closing the unknown-op gap so the sharding lint can run with
    # coverage-as-errors; see analysis/sharding.py RULES for the rule
    # semantics)
    "square": OpSpec(inputs={"X": ONE}, outputs={"Out": ONE},
                     sharding="follow_x"),
    "relu": OpSpec(inputs={"X": ONE}, outputs={"Out": ONE},
                   sharding="follow_x"),
    "sigmoid": OpSpec(inputs={"X": ONE}, outputs={"Out": ONE},
                      sharding="follow_x"),
    "tanh": OpSpec(inputs={"X": ONE}, outputs={"Out": ONE},
                   sharding="follow_x"),
    "gelu": OpSpec(inputs={"X": ONE}, outputs={"Out": ONE},
                   attr_types={"approximate": bool}, sharding="follow_x"),
    "increment": OpSpec(inputs={"X": ONE}, outputs={"Out": ONE},
                        attr_types={"step": _NUM}, sharding="follow_x"),
    "fill_zeros_like": OpSpec(inputs={"X": ONE}, outputs={"Out": ONE},
                              sharding="follow_x"),
    "fill_any_like": OpSpec(inputs={"X": ONE}, outputs={"Out": ONE},
                            attr_types={"value": _NUM, "dtype": str},
                            sharding="follow_x"),
    "equal": OpSpec(inputs={"X": ONE, "Y": ONE}, outputs={"Out": ONE},
                    sharding="elementwise"),
    "square_error_cost": OpSpec(
        inputs={"X": ONE, "Y": ONE}, outputs={"Out": ONE},
        sharding="elementwise"),
    "sigmoid_cross_entropy_with_logits": OpSpec(
        inputs={"X": ONE, "Label": ONE}, outputs={"Out": ONE},
        attr_types={"ignore_index": int, "normalize": bool},
        sharding="elementwise"),
    "reshape2": OpSpec(
        inputs={"X": ONE, "Shape": OPT, "ShapeTensor": ANY},
        outputs={"Out": ONE, "XShape": OPT},
        attr_types={"shape": _LIST}, sharding="reshape"),
    "transpose2": OpSpec(
        inputs={"X": ONE}, outputs={"Out": ONE, "XShape": OPT},
        attr_types={"axis": _LIST}, sharding="transpose"),
    "unsqueeze2": OpSpec(
        inputs={"X": ONE}, outputs={"Out": ONE, "XShape": OPT},
        attr_types={"axes": _LIST}, sharding="unsqueeze"),
    "slice": OpSpec(
        inputs={"Input": ONE}, outputs={"Out": ONE},
        attr_types={"axes": _LIST, "starts": _LIST, "ends": _LIST,
                    "decrease_axis": _LIST},
        sharding="slice"),
    "split": OpSpec(
        inputs={"X": ONE}, outputs={"Out": MANY},
        attr_types={"axis": int, "num": int, "sections": _LIST},
        sharding="split"),
    "gather": OpSpec(
        inputs={"X": ONE, "Index": ONE}, outputs={"Out": ONE},
        attr_types={"axis": int}, sharding="gather"),
    "layer_norm": OpSpec(
        inputs={"X": ONE, "Scale": OPT, "Bias": OPT},
        outputs={"Y": ONE, "Mean": OPT, "Variance": OPT},
        attr_types={"epsilon": _NUM, "begin_norm_axis": int},
        sharding="layer_norm"),
    "lookup_table": OpSpec(
        inputs={"W": ONE, "Ids": ONE}, outputs={"Out": ONE},
        attr_types={"padding_idx": int, "is_sparse": bool},
        sharding="lookup"),
    "lookup_table_v2": OpSpec(
        inputs={"W": ONE, "Ids": ONE}, outputs={"Out": ONE},
        attr_types={"padding_idx": int, "is_sparse": bool},
        sharding="lookup"),
    "lookup_table_sparse_grad": OpSpec(
        inputs={"W": ONE, "Ids": ONE, "OG:Out": ONE},
        outputs={"IG:W": ONE},
        attr_types={"padding_idx": int}, sharding="selected_rows"),
    "fused_attention": OpSpec(
        # Select: a learned selection of (query, key) pairs, one a row;
        # Target: the heads' mean of the probabilities on it
        inputs={"Q": ONE, "K": ONE, "V": ONE, "Mask": OPT, "Select": OPT},
        outputs={"Out": ONE, "Lse": OPT, "Target": OPT},
        attr_types={"scale": _NUM, "dropout": _NUM, "causal": bool,
                    "sequence_parallel": bool, "sp_mode": str,
                    "window": int, "return_target": bool, "layout": str},
        sharding="attention"),
    # --- the sparse-attention indexer (ops/sparse_index.py) ---------------
    "sparse_index": OpSpec(
        inputs={"QI": ONE, "KI": ONE, "W": ONE},
        outputs={"Scores": ONE, "Select": ONE, "PairsPerQuery": OPT},
        required_attrs=("topk",), attr_types={"topk": int},
        sharding="follow_x"),
    "sparse_index_loss": OpSpec(
        inputs={"Scores": ONE, "Select": ONE, "Target": ONE},
        outputs={"Loss": ONE}, sharding="follow_x"),
    "detach": OpSpec(inputs={"X": ONE}, outputs={"Out": ONE},
                     sharding="elementwise"),
    "switch_moe": OpSpec(
        inputs={"X": ONE, "GateW": ONE, "ExpertW1": ONE, "ExpertB1": OPT,
                "ExpertW2": ONE, "ExpertB2": OPT},
        outputs={"Out": ONE, "AuxLoss": OPT, "GateIdx": OPT},
        attr_types={"capacity_factor": _NUM, "top_k": int},
        sharding="moe", cross_batch=True),
    # the expert layer of sparse decoder LMs (ops/moe.py routed_moe): no
    # capacity, so no token's result depends on another's
    "routed_moe": OpSpec(
        # no ExpertGate: experts of the form W_down relu(W_up x)^2; ExpertX:
        # what the experts read where it is not what the router scores
        inputs={"X": ONE, "GateW": ONE, "SelectBias": OPT,
                "ExpertGate": OPT, "ExpertUp": ONE, "ExpertDown": ONE,
                "ExpertX": OPT},
        # H .. Inv: what the forward writes for the op's grad rule
        outputs={"Out": ONE, "TopIdx": OPT, "ExpertLoad": OPT, "H": OPT,
                 "U": OPT, "SortedW": OPT, "Order": OPT, "Inv": OPT},
        required_attrs=("top_k",),
        attr_types={"top_k": int, "routed_scaling": _NUM,
                    "norm_topk": bool, "experts_total": int,
                    "expert_offset": int, "scoring": str, "n_group": int,
                    "topk_group": int, "norm_topk_eps": _NUM},
        sharding="moe"),
    "rms_norm": OpSpec(
        inputs={"X": ONE, "Scale": OPT}, outputs={"Y": ONE},
        attr_types={"epsilon": _NUM}, sharding="follow_x"),
    "rotary_embedding": OpSpec(
        inputs={"X": ONE, "Positions": OPT}, outputs={"Out": ONE},
        attr_types={"theta": _NUM, "rotary_dim": int, "layout": str,
                    "rope_type": str, "factor": _NUM,
                    "original_max_position": int, "beta_fast": _NUM,
                    "beta_slow": _NUM, "scale": _NUM, "sections": _LIST,
                    "rotary_start": int},
        sharding="follow_x"),
    "swiglu": OpSpec(
        inputs={"Gate": ONE, "Up": ONE}, outputs={"Out": ONE},
        sharding="elementwise"),
    "relu2": OpSpec(inputs={"X": ONE}, outputs={"Out": ONE},
                    sharding="elementwise"),
    # --- the state-space mixer (ops/ssm.py) -------------------------------
    "causal_conv1d": OpSpec(
        inputs={"X": ONE, "W": ONE, "Bias": OPT}, outputs={"Out": ONE},
        attr_types={"activation": str}, sharding="follow_x"),
    "gated_short_conv": OpSpec(
        inputs={"X": ONE, "W": ONE}, outputs={"Out": ONE},
        sharding="follow_x"),
    "ssm_scan": OpSpec(
        inputs={"X": ONE, "B": ONE, "C": ONE, "Dt": ONE, "DtBias": ONE,
                "ALog": ONE, "D": ONE},
        # States .. CumA: what the forward writes for the op's grad rule
        outputs={"Y": ONE, "States": OPT, "DtSoft": OPT, "CumA": OPT},
        required_attrs=("chunk_size",), attr_types={"chunk_size": int},
        sharding="follow_x"),
    "gated_group_rms_norm": OpSpec(
        inputs={"X": ONE, "Gate": ONE, "Scale": OPT}, outputs={"Y": ONE},
        attr_types={"groups": int, "epsilon": _NUM}, sharding="follow_x"),
    # --- the gated delta rule (ops/kda.py) --------------------------------
    "kda_gate": OpSpec(
        inputs={"X": ONE, "ALog": ONE, "DtBias": ONE}, outputs={"G": ONE},
        # lower_bound: the bounded form; absent, -exp(ALog) softplus(.)
        attr_types={"lower_bound": _NUM}, sharding="follow_x"),
    "kda_scan": OpSpec(
        inputs={"Q": ONE, "K": ONE, "V": ONE, "G": ONE, "Beta": ONE},
        # States: what the forward writes for the op's grad rule
        outputs={"Y": ONE, "States": OPT},
        required_attrs=("chunk_size",),
        attr_types={"chunk_size": int, "lower_bound": _NUM,
                    "beta_scale": _NUM},
        sharding="follow_x"),
    "l2_norm": OpSpec(inputs={"X": ONE}, outputs={"Out": ONE},
                      attr_types={"epsilon": _NUM, "scale": _NUM},
                      sharding="elementwise"),
    "head_gate": OpSpec(inputs={"X": ONE, "Gate": ONE}, outputs={"Out": ONE},
                        sharding="follow_x"),
    # --- serving tier: paged KV-cache decode ops (ops/paged_ops.py) ------
    # sharding "replicated": serving parallelism is whole-model replicas
    # behind the round-robin frontend (serving/frontend.py) — the pools
    # and page tables are per-replica state, never mesh-sharded.
    # kv_scale (static dequant scale) flips the pools to int8 KV;
    # use_kernel / max_blocks pick the fused-Pallas read path and bound
    # the page-table walk (ops/pallas/paged_attention.py); span (> 1, the
    # speculative-decoding verify step) makes KNew/VNew/Q position-major
    # [B, span*nh*hd] runs written/scored at Pos..Pos+span-1 — all
    # trace-time-static attrs, so the specs stay closed.
    "paged_cache_update": OpSpec(
        inputs={"KPool": ONE, "VPool": ONE, "KNew": ONE, "VNew": ONE,
                "PageTable": ONE, "Pos": ONE},
        outputs={"KPoolOut": ONE, "VPoolOut": ONE},
        required_attrs=("block_size",),
        attr_types={"block_size": int, "kv_scale": _NUM, "span": int},
        closed_attrs=True, sharding="replicated"),
    "paged_attention": OpSpec(
        inputs={"Q": ONE, "KPool": ONE, "VPool": ONE, "PageTable": ONE,
                "Pos": ONE},
        outputs={"Out": ONE},
        required_attrs=("block_size",),
        attr_types={"block_size": int, "use_kernel": bool,
                    "max_blocks": int, "kv_scale": _NUM, "span": int},
        closed_attrs=True, sharding="replicated"),
    # --- decode/search ops (ops/decode_ops.py) ---------------------------
    "linear_chain_crf": OpSpec(
        inputs={"Emission": ONE, "Transition": ONE, "Label": ONE,
                "SeqLen": OPT},
        outputs={"LogLikelihood": ONE, "Alpha": OPT, "EmissionExps": OPT,
                 "TransitionExps": OPT},
        sharding="follow_x"),
    "crf_decoding": OpSpec(
        inputs={"Emission": ONE, "Transition": ONE, "Label": OPT,
                "SeqLen": OPT},
        outputs={"ViterbiPath": ONE}, sharding="follow_x"),
    "gather_tree": OpSpec(
        inputs={"Ids": ONE, "Parents": ONE}, outputs={"Out": ONE},
        sharding="follow_x"),
    "beam_search": OpSpec(
        inputs={"pre_ids": ONE, "pre_scores": ONE, "scores": ONE,
                "ids": OPT},
        outputs={"selected_ids": ONE, "selected_scores": ONE,
                 "parent_idx": ONE},
        required_attrs=("beam_size",),
        attr_types={"beam_size": int, "end_id": int},
        sharding="follow_x"),
    "beam_search_decode": OpSpec(
        inputs={"Ids": ONE, "Scores": ONE, "Parents": ONE},
        outputs={"SentenceIds": ONE, "SentenceScores": ONE},
        sharding="follow_x"),
    "auc": OpSpec(
        inputs={"Predict": ONE, "Label": ONE, "StatPos": ONE,
                "StatNeg": ONE},
        outputs={"AUC": ONE, "StatPosOut": ONE, "StatNegOut": ONE},
        attr_types={"num_thresholds": int},
        sharding="auc", cross_batch=True),
    "batch_norm": OpSpec(
        inputs={"X": ONE, "Scale": OPT, "Bias": OPT, "Mean": OPT,
                "Variance": OPT},
        outputs={"Y": ONE, "MeanOut": OPT, "VarianceOut": OPT,
                 "SavedMean": OPT, "SavedVariance": OPT},
        attr_types={"epsilon": _NUM, "momentum": _NUM, "is_test": bool},
        sharding="follow_x", cross_batch=True),
}

for _name in ("elementwise_add", "elementwise_sub", "elementwise_mul",
              "elementwise_div", "elementwise_min", "elementwise_max",
              "elementwise_pow", "elementwise_mod"):
    SPECS[_name] = OpSpec(inputs={"X": ONE, "Y": ONE}, outputs={"Out": ONE},
                          attr_types={"axis": int}, sharding="elementwise")

# Cross-batch ops WITHOUT a full slot spec yet (the remaining sync-BN
# family): the fallback matrix must still know them. Grow a full OpSpec
# (and drop the name here) when the lint zoo first emits one.
_EXTRA_CROSS_BATCH: FrozenSet[str] = frozenset({"data_norm", "inplace_abn"})


def cross_batch_ops() -> FrozenSet[str]:
    """THE cross-batch op table (single source): op types whose semantics
    couple examples across the global batch, so a manual-dp shard would
    silently compute per-shard statistics. Consumed by parallel/zero.py
    (runtime decline, counted under `zero_manual_fallbacks.<cause>`) and
    by analysis/sharding.py (the build-time lint naming the op)."""
    return frozenset(n for n, s in SPECS.items() if s.cross_batch) \
        | _EXTRA_CROSS_BATCH


# the normalization/batch-stats family keeps its historical dedicated
# fallback counter; every other cross-batch op counts under the generic
# cause. ONE mapping — the runtime counter (zero.count_fallback) and the
# lint's predicted counter name come from here and cannot drift.
_BATCH_STATS_OPS = frozenset({"batch_norm", "data_norm", "inplace_abn"})


def cross_batch_cause(op_type: str) -> str:
    """The `zero_manual_fallbacks.<cause>` suffix a cross-batch op counts
    under at run time ("batch_norm" for the sync-BN family,
    "cross_batch" otherwise)."""
    return "batch_norm" if op_type in _BATCH_STATS_OPS else "cross_batch"


def install() -> None:
    """Idempotently attach the spec table to the op registry."""
    for name, spec in SPECS.items():
        registry.set_spec(name, spec)


install()
