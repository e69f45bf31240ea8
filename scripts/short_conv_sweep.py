#!/usr/bin/env python
"""The sweep behind `ops/ssm.py`'s `gated_short_conv`: one layer's gates and
three-tap convolution at the short-convolution cell's size (`[1, 8192, 3 x
2048]` bf16), forward and backward, as the shipped form and as the other
`jax.numpy` forms it was chosen among; milliseconds a launch on the host's
clock and the share of the least time the bytes take (8 x 2048 B a token
forward, 14 x 2048 backward).

    python scripts/short_conv_sweep.py [--forms shipped,f32_pad,shifted_inputs]

A time only on a TPU; elsewhere it refuses. Writes
chiprun_out/short_conv_sweep.json.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

B, S, C, K = 1, 8192, 2048, 3
HBM_BYTES_PER_S = 819e9


def forms():
    """{name: f(bcx, w) -> y}; each differentiated by JAX but `shipped`,
    which brings its own backward."""
    import jax.numpy as jnp
    from paddle_tpu.ops import ssm
    f32 = jnp.float32

    def thirds(bcx):
        return bcx[..., :C], bcx[..., C:2 * C], bcx[..., 2 * C:]

    def f32_pad(bcx, w):
        """The first gate's product widened, padded and read three times."""
        b, c, u = thirds(bcx)
        padded = jnp.pad((b * u).astype(f32), ((0, 0), (K - 1, 0), (0, 0)))
        conv = sum(padded[:, j:j + S] * w[j] for j in range(K))
        return c * conv.astype(bcx.dtype)

    def shifted_inputs(bcx, w):
        """B and u moved, not their product: nothing between the projection
        and y has to be written."""
        b, c, u = thirds(bcx)
        pb = jnp.pad(b, ((0, 0), (K - 1, 0), (0, 0)))
        pu = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
        conv = sum((pb[:, j:j + S] * pu[:, j:j + S]).astype(f32) * w[j]
                   for j in range(K))
        return c * conv.astype(bcx.dtype)

    return {"shipped": ssm._gated_conv, "f32_pad": f32_pad,
            "shifted_inputs": shifted_inputs}


def timed(fn, args, launches=20, rounds=3):
    """The best of `rounds` rounds of `launches` launches, after three that
    are not timed: the first form timed in a process reads slower."""
    import jax
    for _ in range(3):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(launches):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, 1e3 * (time.perf_counter() - t0) / launches)
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--forms", default="shipped,f32_pad,shifted_inputs")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("short_conv_sweep: needs a TPU", file=sys.stderr)
        return 2
    rng = np.random.RandomState(3)
    bcx = jnp.asarray(rng.randn(B, S, 3 * C), jnp.bfloat16)
    dy = jnp.asarray(rng.randn(B, S, C), jnp.bfloat16)
    w = jnp.asarray(rng.randn(K, C) * 0.02, jnp.float32)
    least = {"fwd": 8 * C * B * S / HBM_BYTES_PER_S * 1e3,
             "bwd": 14 * C * B * S / HBM_BYTES_PER_S * 1e3}
    rows, want = {}, None
    for name in args.forms.split(","):
        f = forms()[name]
        fwd = jax.jit(f)
        bwd = jax.jit(lambda bcx, w, dy, f=f: jax.vjp(f, bcx, w)[1](dy))
        got = [np.asarray(t, np.float32) for t in (fwd(bcx, w),
                                                   *bwd(bcx, w, dy))]
        want = want or got
        ms = {"fwd": timed(fwd, (bcx, w)), "bwd": timed(bwd, (bcx, w, dy))}
        rows[name] = {
            "ms": ms, "bytes_least_share": {k: least[k] / ms[k] for k in ms},
            "gap_to_first": [float(np.abs(a - b).max() / np.abs(b).max())
                             for a, b in zip(got, want)]}
        print(f"[short_conv_sweep] {name}: {rows[name]}", flush=True)
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/short_conv_sweep.json", "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
