"""Seconds the program's package took to import (`startup.import`: top to
bottom of `paddle_tpu/__init__.py`, JAX already imported by the harness)."""
from benchmark import spans


def read(ctx):
    return spans.seconds(spans.outermost(spans.of(ctx), {"startup.import"}))
