"""The fullest held expert's assignments over the mean held expert's, per
layer and step: the program's gauge `moe.load_max_over_mean` as each
reading of the window left it, averaged. 1.0 is even routing; the seeded
selection bias makes it uneven."""
import statistics


def read(ctx):
    got = [r["routing"]["load_max_over_mean"]
           for r in ctx.get("readings", []) if r.get("routing")]
    return statistics.mean(got) if got else None
