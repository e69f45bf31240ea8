"""The grouped matmuls' share of their roofline where a rank holds 32
experts of 2048 x 512 behind a dense layer: the least time the chip could
take for the experts' nine matmuls of the traced steps at the assignments
the program counted there, in the sparse layers alone
(benchmark/counts_gated_gqa.py), over the time under the `moe.experts`
scope and of the grouped-matmul kernels (`ragged-dot`) in the trace."""
import statistics

from benchmark import counts, counts_gated_gqa, scopes


def read(ctx):
    if ctx["kind"] != "train":
        return None
    taken = scopes.group_seconds(ctx, ("moe.experts",), ("ragged-dot",))
    traced = [r["routing"]["local_assignments_per_token"]
              for r in ctx["readings"][1:1 + ctx["traced_readings"]]
              if r.get("routing")]
    if not taken or not traced:
        return None
    flops, nbytes = counts_gated_gqa.moe_experts_train_flops_bytes(
        ctx["cfg"], statistics.mean(traced) * ctx["rows"] * ctx["seq"])
    least, _ = counts.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * ctx["traced_readings"] * ctx["k"] * least / taken
