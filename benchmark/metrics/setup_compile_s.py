"""Seconds in XLA's backend for the program's steps (`compile.backend`
under `executor.step` roots): a compilation, or the fetch of the
executable from the persistent cache, which counts."""
from benchmark import spans


def read(ctx):
    return spans.seconds(spans.under_roots(
        spans.of(ctx), {"compile.backend"}))
