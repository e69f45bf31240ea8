"""Seconds spent building the Program: the model's graph (`program.build`)
and `minimize` (backward, optimizer ops, the fleet strategy's passes:
`optimizer.minimize`, the outermost where the fleet wrapper holds the
inner optimizer's)."""
from benchmark import spans


def read(ctx):
    return spans.seconds(spans.outermost(
        spans.of(ctx), {"program.build", "optimizer.minimize"}))
