"""Seconds JAX spent tracing the program's steps to jaxprs and lowering
them to StableHLO (`compile.trace` + `compile.lower` under `executor.step`
roots; nested traces are part of their outermost). Python time that the
persistent compile cache does not save."""
from benchmark import spans


def read(ctx):
    return spans.seconds(spans.under_roots(
        spans.of(ctx), {"compile.trace", "compile.lower"}))
