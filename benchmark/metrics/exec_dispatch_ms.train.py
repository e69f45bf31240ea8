"""Host time from the `run_steps` call to its return (the fetch left
unsynced), median over the window's readings."""
import statistics


def read(ctx):
    if ctx["kind"] != "train" or not ctx["readings"]:
        return None
    return 1e3 * statistics.median(r["dispatch_s"] for r in ctx["readings"])
