"""Model FLOP/s utilization of a causal-LM training cell whose layers mix by
a gated short convolution or by attention: `train_tok_s` times the
operations one token needs (benchmark/counts_conv_gqa.py: each layer's mixer
by kind, the dense part or the router and the routed experts at the
assignments that really fell on a held expert in the window's readings, the
tied head over the vocabulary held; nothing recomputed) over the bf16
peak."""
import statistics

from benchmark import counts_conv_gqa


def read(ctx):
    per_tok = [r["routing"]["local_assignments_per_token"]
               for r in ctx.get("readings", []) if r.get("routing")]
    if ctx["kind"] != "train" or not per_tok:
        return None
    flops = counts_conv_gqa.lm_train_flops_per_token(
        ctx["cfg"], ctx["seq"], statistics.mean(per_tok))
    return 100.0 * ctx["train_tok_s"] * flops / (
        ctx["chips"] * ctx["peaks"]["bf16_flops"])
