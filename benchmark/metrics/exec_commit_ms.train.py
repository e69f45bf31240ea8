"""Median host milliseconds of `executor.commit` over the window's
readings: after the jitted call, the new state written to the scope, the
snapshot hook, the fetches packaged."""
from benchmark import spans


def read(ctx):
    return spans.window_phase_ms(ctx, "executor.commit")
