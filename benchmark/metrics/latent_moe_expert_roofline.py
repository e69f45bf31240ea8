"""The grouped matmuls' share of their roofline where the experts live in a
latent, two matrices [latent, f] and [f, latent] an expert: the least time
the chip could take for the experts' matmuls of the traced steps at the
assignments the program counted there (benchmark/counts_latent_hybrid.py:
the counted assignments, not the row buffer's rows, so the share cannot pass
100 % whatever the buffer holds) over the time of the grouped-matmul kernels
(`ragged-dot`) in the trace."""
import statistics

from benchmark import counts, counts_latent_hybrid, scopes


def read(ctx):
    if ctx["kind"] != "train" or "moe_latent_size" not in ctx["cfg"]:
        return None
    taken = scopes.group_seconds(ctx, (), ("ragged-dot",))
    traced = [r["routing"]["local_assignments_per_token"]
              for r in ctx["readings"][1:1 + ctx["traced_readings"]]
              if r.get("routing")]
    if not taken or not traced:
        return None
    flops, nbytes = counts_latent_hybrid.moe_experts_train_flops_bytes(
        ctx["cfg"], statistics.mean(traced) * ctx["rows"] * ctx["seq"])
    least, _ = counts.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * ctx["traced_readings"] * ctx["k"] * least / taken
