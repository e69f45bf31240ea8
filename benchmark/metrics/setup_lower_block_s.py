"""Seconds the program's own op lowerings took while JAX traced its
steps (outermost `executor.lower_block` under `executor.step` roots: the
walks of the op list, shapes-only ones too). `setup_trace_lower_s` less
it is JAX's own tracing and MLIR."""
from benchmark import spans


def read(ctx):
    return spans.seconds(spans.under_roots(
        spans.of(ctx), {"executor.lower_block"}))
