"""Model FLOP/s utilization of a causal-LM training cell whose query-head
count differs by layer: `train_tok_s` times the operations one token needs
(benchmark/counts_gated_gqa.py: q, the gate and the output projection at
each layer's own head count, k and v at the KV heads', attention over the
pairs each kind of layer sees, the dense layer, the router, the shared
expert and the routed experts at the assignments that really fell on a held
expert in the window's readings, the head over the vocabulary held) over
the bf16 peak: the share of the whole step."""
import statistics

from benchmark import counts_gated_gqa


def read(ctx):
    per_tok = [r["routing"]["local_assignments_per_token"]
               for r in ctx.get("readings", []) if r.get("routing")]
    if ctx["kind"] != "train" or not per_tok:
        return None
    flops = counts_gated_gqa.lm_train_flops_per_token(
        ctx["cfg"], ctx["seq"], statistics.mean(per_tok))
    return 100.0 * ctx["train_tok_s"] * flops / (
        ctx["chips"] * ctx["peaks"]["bf16_flops"])
