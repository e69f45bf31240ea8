"""Median over the window's readings of one reading's time (`run_steps(k)`
ending in the host read of its k losses). `train_tok_s` is all tokens over
the whole window; where it falls and this does not, a few readings
stalled, and series.json says which."""
import statistics


def read(ctx):
    if ctx["kind"] != "train" or not ctx["readings"]:
        return None
    return 1e3 * statistics.median(r["seconds"] for r in ctx["readings"])
