"""Seconds in executor dispatches of the startup program (`executor.step`
roots with `program: "startup"`): initialisers traced, fetched from the
cache or compiled, and run."""
from benchmark import spans


def read(ctx):
    return spans.seconds(spans.roots(spans.of(ctx), program="startup"))
