"""Programs compiled (or fetched from the persistent cache) between the
first and the last reading: the executor's `compile_cache_misses` rise
plus JAX's own backend-compile events. Should read 0."""


def read(ctx):
    if ctx["kind"] != "train":
        return None
    return float(ctx["compiles_in_window"])
