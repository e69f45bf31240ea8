"""Model FLOP/s utilization of a linear-attention / latent-attention
causal-LM training cell: `train_tok_s` times the operations one token needs
by layer kind (benchmark/counts_kda_mla.py: the delta rule's projections
and the rule as the recurrence, latent attention over the causal pairs at
the held heads, the dense part, the shared and the routed experts at the
assignments that really fell on a held expert in the window's readings, the
head over the vocabulary held) over the bf16 peak."""
import statistics

from benchmark import counts_kda_mla


def read(ctx):
    per_tok = [r["routing"]["local_assignments_per_token"]
               for r in ctx.get("readings", []) if r.get("routing")]
    if ctx["kind"] != "train" or not per_tok:
        return None
    flops = counts_kda_mla.lm_train_flops_per_token(
        ctx["cfg"], ctx["seq"], statistics.mean(per_tok))
    return 100.0 * ctx["train_tok_s"] * flops / (
        ctx["chips"] * ctx["peaks"]["bf16_flops"])
