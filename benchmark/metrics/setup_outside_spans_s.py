"""Seconds of set-up that neither the program nor JAX names: from the
start of `startup.boot` to the start of the window's first root (the
first of the last len(readings) `run_steps` roots of the cell's `k`),
less the union of every span of the ring in that interval, the harness's
parentless `compile.*` included. What is left is the harness's numpy
traffic, `device_put`, host reads of device results and `gc`."""
from benchmark import spans


def read(ctx):
    if ctx["kind"] != "train" or not ctx["readings"]:
        return None
    evs = spans.of(ctx)
    boot = spans.outermost(evs, {"startup.boot"})
    window = spans.roots(evs, kind="run_steps", k=ctx["k"])[
        -len(ctx["readings"]):]
    if not boot or not window:
        return None
    lo, hi = boot[0]["ts"], window[0]["ts"]
    covered, cur = 0.0, lo
    for a, b in sorted((e["ts"], e["ts"] + e["dur"]) for e in evs):
        a, b = max(a, cur), min(b, hi)
        if b > a:
            covered += b - a
            cur = b
    return 1e-6 * (hi - lo - covered)
