"""Median host milliseconds of `executor.launch` over the window's
readings: the call of the jitted step until it returns (the device is
left running)."""
from benchmark import spans


def read(ctx):
    return spans.window_phase_ms(ctx, "executor.launch")
