"""Autocast: per-op white/black list dtype casting.

Reference: contrib/mixed_precision/fp16_lists.py:38 (op lists) +
imperative/amp_auto_cast.cc (tracer hook). Same structure: MXU-friendly ops
(matmul/conv) run in low precision; numerically sensitive ops stay float32.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp

# ops cast to low precision (reference white list: compute-bound MXU ops)
white_list = {
    "conv2d", "depthwise_conv2d", "conv2d_transpose", "matmul", "matmul_v2",
    # chunked LM head: bf16 operands are safe — every einsum accumulates
    # f32 (preferred_element_type) and the loss returns f32 (ops/fused_ce.py)
    "fused_lm_head_ce",
    "mul", "bmm", "fc",
    # the experts' grouped matmuls run on bf16 operands; the router does
    # not (keep_f32_slots below)
    "routed_moe",
    # q, k, v and the cotangent of Out reach the attention matmuls (dense or
    # the flash kernels) in the compute dtype, like every other matmul's
    # operands; every dot accumulates f32, softmax and logsumexp stay f32
    "fused_attention",
    # the scan's matmuls (C . B, the decayed products, the states read by
    # C) run on bf16 operands; the step, A and D do not (keep_f32_slots)
    "ssm_scan",
    # the delta rule's matmuls (the decayed products, the states as a
    # factor) run on bf16 operands; the decay and beta do not
    "kda_scan",
    # the indexer's score products run on bf16 operands like any matmul of
    # the step; its per-head weights do not (keep_f32_slots), and relu,
    # weighting, the sum over heads and the selection are float32 inside
    "sparse_index",
}
# per-op input slots excluded from the white-list cast: tiny O(V)/O(H)
# operands whose quantization buys no MXU time but drifts parity with the
# dense path (which applies them in f32 via non-white-listed elementwise
# ops)
keep_f32_slots = {
    "fused_lm_head_ce": {"Bias"},
    # the flash kernels' logsumexp residual is float32 (the grad op reads it
    # as FO:Lse), and so is the additive mask: O(B*S) for key padding, added
    # to the f32 scores, widened per block by the kernels anyway
    "fused_attention": {"Lse", "Mask"},
    # the router scores tokens in float32 from float32 activations: a
    # bf16 rounding of either moves which experts a near-tie selects; the
    # slots' weights it made reach the grad op as they were (FO:SortedW)
    "routed_moe": {"X", "GateW", "SelectBias", "SortedW"},
    # the step before its softplus, the per-head parameters, and what the
    # forward wrote for the grad op (FO:States, FO:DtSoft, FO:CumA): the
    # decays, their running sums and the states are float32
    "ssm_scan": {"Dt", "DtBias", "ALog", "D", "States", "DtSoft", "CumA"},
    # a channel's log decay, beta before its sigmoid, and the chunk states
    # the forward wrote for the grad op (FO:States) are float32
    "kda_scan": {"G", "Beta", "States"},
    # the weights the relu'd products are summed with, and the scores'
    # cotangent the grad op reads (OG:Scores)
    "sparse_index": {"W", "Scores"},
}

# ops forced to float32 (reference black list: reductions/normalizations)
black_list = {
    "softmax", "softmax_with_cross_entropy", "cross_entropy", "layer_norm",
    "rms_norm",
    "batch_norm", "mean", "reduce_mean", "reduce_sum", "sum", "exp", "log",
    "square", "p_norm", "sigmoid_cross_entropy_with_logits",
}


def maybe_autocast_inputs(op_type, in_map, low_dtype):
    """Called by the dygraph tracer when amp level is O1."""
    if op_type in white_list:
        target = low_dtype
    elif op_type in black_list:
        target = jnp.float32
    else:
        return in_map
    skip = keep_f32_slots.get(op_type, ())
    out = {}
    for slot, ts in in_map.items():
        if slot in skip:
            out[slot] = ts
            continue
        cast_ts = []
        for t in ts:
            v = t.value
            if v is not None and jnp.issubdtype(v.dtype, jnp.floating) \
                    and v.dtype != target:
                from ..dygraph.tracer import Tensor
                nt = Tensor(v.astype(target),
                            stop_gradient=t.stop_gradient)
                nt.is_leaf = t.is_leaf
                nt.grad_node = t.grad_node
                # chain a cast node so grads flow back in the original dtype
                if not t.stop_gradient:
                    from ..dygraph.tracer import TapeNode, current_tracer
                    src_dtype = v.dtype

                    def vjp_fn(cts, _d=src_dtype):
                        return (cts[0].astype(_d),)
                    node = TapeNode("autocast", vjp_fn, [t], [nt],
                                    current_tracer().next_node_idx())
                    nt.grad_node = node
                    nt.is_leaf = False
                cast_ts.append(nt)
            else:
                cast_ts.append(t)
        out[slot] = cast_ts
    return out


@contextlib.contextmanager
def auto_cast(enable=True, custom_white_list=None, custom_black_list=None,
              level="O1", dtype="bfloat16"):
    """paddle.amp.auto_cast context (reference amp/auto_cast.py:20)."""
    from ..framework.program import in_dygraph_mode
    from ..dygraph.tracer import current_tracer
    added_w = set(custom_white_list or ())
    added_b = set(custom_black_list or ())
    white_list.update(added_w)
    black_list.update(added_b)
    tracer = current_tracer() if in_dygraph_mode() else None
    old_level = tracer._amp_level if tracer else "O0"
    if tracer and enable:
        tracer._amp_level = level
        tracer._amp_dtype = (jnp.bfloat16 if dtype == "bfloat16"
                             else jnp.float16)
    try:
        yield
    finally:
        if tracer:
            tracer._amp_level = old_level
        white_list.difference_update(added_w)
        black_list.difference_update(added_b)


amp_guard = auto_cast
