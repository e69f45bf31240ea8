"""Model FLOP/s utilization of a causal-LM training cell: `train_tok_s`
times the operations one token needs (benchmark/counts_mla_moe.py:
projections, causal attention at its two widths, dense and shared FFNs,
router, the routed experts at the assignments that really fell on a held
expert in the window's readings, the head over the vocabulary held;
lookups and recomputation not counted) over the bf16 peak."""
import statistics

from benchmark import counts_mla_moe


def read(ctx):
    per_tok = [r["routing"]["local_assignments_per_token"]
               for r in ctx.get("readings", []) if r.get("routing")]
    if ctx["kind"] != "train" or not per_tok:
        return None
    flops = counts_mla_moe.lm_train_flops_per_token(
        ctx["cfg"], ctx["seq"], statistics.mean(per_tok))
    return 100.0 * ctx["train_tok_s"] * flops / (
        ctx["chips"] * ctx["peaks"]["bf16_flops"])
