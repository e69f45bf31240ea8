"""Share of the traced stretch in which device 0 ran nothing WHILE the
program's executor had the host: the device's idle gaps cut with the
`pt/executor.step` host events of the same xplane (the program's root
spans, written by `jax.profiler.TraceAnnotation` on the profiler's own
clock). `device_idle_pct.train` less this is idle time while the caller,
not the program, had the host."""
import glob
import os

import numpy as np

from benchmark import common, xplane

ROOT_EVENT = "pt/executor.step"


def newest_xplane(out_root: str):
    files = glob.glob(os.path.join(out_root, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def gaps_and_roots(path: str):
    """(device 0's idle gaps, the root spans' intervals), merged, in ns."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    line = xplane._line(xplane._device_planes(pd)[0], xplane.OPS_LINE)
    busy = xplane.union(xplane._intervals(xplane._leaf_ops(line.events)))
    gaps = xplane.subtract(np.array([[busy[0][0], busy[-1][1]]]), busy)
    names, spans = xplane._host_events(pd)
    mine = [i for i, n in enumerate(names) if n == ROOT_EVENT]
    return gaps, xplane.union(spans[mine])


def idle_under(gaps: np.ndarray, roots: np.ndarray) -> float:
    """Nanoseconds of `gaps` that `roots` cover."""
    return xplane.total(gaps) - xplane.total(xplane.subtract(gaps, roots))


def read(ctx):
    tr = ctx.get("trace")
    if ctx["kind"] != "train" or not tr:
        return None
    path = newest_xplane(common.OUT_ROOT)
    if path is None:
        return None
    gaps, roots = gaps_and_roots(path)
    if not len(roots):
        return None
    return 100.0 * idle_under(gaps, roots) * 1e-9 / tr["window_s"]
