"""Seconds of `setup_compile_s` that were real compilations: the
`compile.backend` spans under `executor.step` roots whose `cache` arg is
not `"hit"` (`"miss_written"`, `"miss"`: compiled and not even written,
`"off"`). `setup_compile_s` less it is what was fetched. A program whose
spans do not say what the cache did (the parent) gives nothing."""
from benchmark import spans


def read(ctx):
    backend = [e for e in spans.under_roots(spans.of(ctx),
                                            {"compile.backend"})
               if "cache" in e.get("args", {})]
    if not backend:
        return None
    return 1e-6 * sum(e["dur"] for e in backend
                      if e["args"]["cache"] != "hit")
