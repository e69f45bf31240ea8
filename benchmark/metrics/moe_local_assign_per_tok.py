"""(token, slot) choices that fell on an expert held here, per token: the
program's gauge `moe.local_assignments_per_token` as each reading of the
window left it, averaged. 0.75 when 6 choices spread evenly over 128
experts of which 16 are here."""
import statistics


def read(ctx):
    got = [r["routing"]["local_assignments_per_token"]
           for r in ctx.get("readings", []) if r.get("routing")]
    return statistics.mean(got) if got else None
