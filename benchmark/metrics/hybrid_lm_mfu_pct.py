"""Model FLOP/s utilization of a hybrid causal-LM training cell:
`train_tok_s` times the operations one token needs by layer kind
(benchmark/counts_hybrid_ssm.py: the state-space mixer's projections and
its scan as the recurrence, the shared and the routed experts at the
assignments that really fell on a held expert in the window's readings,
attention over the causal pairs, the head over the vocabulary held) over
the bf16 peak."""
import statistics

from benchmark import counts_hybrid_ssm


def read(ctx):
    per_tok = [r["routing"]["local_assignments_per_token"]
               for r in ctx.get("readings", []) if r.get("routing")]
    if ctx["kind"] != "train" or not per_tok:
        return None
    flops = counts_hybrid_ssm.lm_train_flops_per_token(
        ctx["cfg"], ctx["seq"], statistics.mean(per_tok))
    return 100.0 * ctx["train_tok_s"] * flops / (
        ctx["chips"] * ctx["peaks"]["bf16_flops"])
