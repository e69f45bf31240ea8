"""Model FLOP/s utilization of a delta-rule / gated-softmax causal-LM
training cell whose every layer is sparse: `train_tok_s` times the
operations one token needs by layer kind (benchmark/counts_kda_gqa.py: the
delta rule's projections with both low-rank pairs and the rule as the
recurrence, softmax attention over the causal pairs at the held heads with
its gate, the router, the shared and the routed experts at the assignments
that really fell on a held expert in the window's readings, the head over
the vocabulary held) over the bf16 peak."""
import statistics

from benchmark import counts_kda_gqa


def read(ctx):
    per_tok = [r["routing"]["local_assignments_per_token"]
               for r in ctx.get("readings", []) if r.get("routing")]
    if ctx["kind"] != "train" or not per_tok:
        return None
    flops = counts_kda_gqa.lm_train_flops_per_token(
        ctx["cfg"], ctx["seq"], statistics.mean(per_tok))
    return 100.0 * ctx["train_tok_s"] * flops / (
        ctx["chips"] * ctx["peaks"]["bf16_flops"])
