"""Model FLOP/s utilization of a causal-LM training cell whose attention
runs over a learned selection: `train_tok_s` times the operations one token
needs (benchmark/counts_dsa_gqa.py: projections, attention over the
SELECTED pairs, the indexer's scores over the causal pairs forward and the
selected pairs backward, its target, router, the routed experts at the
assignments that really fell on a held expert in the window's readings, the
head over the vocabulary held) over the bf16 peak."""
import statistics

from benchmark import counts_dsa_gqa


def read(ctx):
    per_tok = [r["routing"]["local_assignments_per_token"]
               for r in ctx.get("readings", []) if r.get("routing")]
    if ctx["kind"] != "train" or not per_tok:
        return None
    flops = counts_dsa_gqa.lm_train_flops_per_token(
        ctx["cfg"], ctx["seq"], statistics.mean(per_tok))
    return 100.0 * ctx["train_tok_s"] * flops / (
        ctx["chips"] * ctx["peaks"]["bf16_flops"])
