"""Seconds from the OS's creation of the process to the first line of
`paddle_tpu/__init__.py` (`startup.boot`): the interpreter's start, what
the caller imported first (the harness: `jax`) and the backend it started
(the TPU), before the program was entered."""
from benchmark import spans


def read(ctx):
    return spans.seconds(spans.outermost(spans.of(ctx), {"startup.boot"}))
