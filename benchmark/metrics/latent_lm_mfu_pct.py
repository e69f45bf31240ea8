"""Model FLOP/s utilization of a hybrid causal-LM training cell whose
experts live in a latent: `train_tok_s` times the operations one token needs
by layer kind (benchmark/counts_latent_hybrid.py: the state-space mixer's
projections and its scan as the recurrence at the heads held, attention over
the causal pairs at the heads held, the router, the shared expert and the two
latent projections on every token, the routed experts at the assignments that
really fell on a held expert in the window's readings, the head over the
vocabulary held) over the bf16 peak."""
import statistics

from benchmark import counts_latent_hybrid


def read(ctx):
    per_tok = [r["routing"]["local_assignments_per_token"]
               for r in ctx.get("readings", []) if r.get("routing")]
    if (ctx["kind"] != "train" or not per_tok
            or "moe_latent_size" not in ctx["cfg"]):
        return None
    flops = counts_latent_hybrid.lm_train_flops_per_token(
        ctx["cfg"], ctx["seq"], statistics.mean(per_tok))
    return 100.0 * ctx["train_tok_s"] * flops / (
        ctx["chips"] * ctx["peaks"]["bf16_flops"])
