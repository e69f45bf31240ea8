"""Median host milliseconds of `executor.prepare` over the window's
readings: entry of `run_steps` to just before the jitted call (feed
normalisation, state names, cache key, the random key, state gather)."""
from benchmark import spans


def read(ctx):
    return spans.window_phase_ms(ctx, "executor.prepare")
