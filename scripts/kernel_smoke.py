#!/usr/bin/env python
"""Pallas kernel smoke: interpret-mode bit parity + the census proof.

The CI leg of the fused-kernel pair (ops/pallas/): scripts/ci.py runs
this overlapped with the test shards (--no-kernel-smoke skips). Three
legs, all on the CPU interpreter (interpret=True — same kernel bodies
Mosaic compiles on hardware):

* **decode parity** — fused paged-attention (paged_attention.py) vs the
  dense-gather oracle (ops/paged_ops.paged_attend), BITWISE, across
  block sizes, a bounded max_blocks hint, bf16 pools and the int8-KV
  arm;
* **optimizer parity** — the fused flat-bucket update (zero_update.py)
  vs the jitted registry rule (ops/optimizer_ops.py) BITWISE for
  sgd/momentum/adam/adamw over flat and @LAYERS-stacked buckets;
* **census** — the engine's compiled decode-window HLO carries ZERO
  dense cache-view materializations with the kernel on and the expected
  gather chain with it off (serving/audit.py), and engine tokens match
  kernel on vs off.

Usage (a CPU tool on any machine: re-execs into a CPU-mesh child when the
environment is not one, and says on stderr that it ran on the CPU; Mosaic
compiles the same kernels only in chip_smoke.py's `kernels` leg):

  python scripts/kernel_smoke.py
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _decode_cases(rng):
    import numpy as np
    cases = []
    for bs in (8, 16, 32):
        b, nh, hd, mb, nb = 3, 2, 16, 4, 3 * 4 + 2
        pt = rng.permutation(nb)[: b * mb].reshape(b, mb).astype(np.int32)
        pos = rng.randint(0, mb * bs, (b,)).astype(np.int32)
        q = rng.randn(b, nh, 1, hd).astype(np.float32)
        kp = rng.randn(2, nb, nh, bs, hd).astype(np.float32)
        vp = rng.randn(2, nb, nh, bs, hd).astype(np.float32)
        cases.append((bs, q, kp, vp, pt, pos))
    return cases


def check_decode_parity() -> list:
    import numpy as np
    import jax.numpy as jnp
    from paddle_tpu.ops.paged_ops import paged_attend, quantize_kv
    from paddle_tpu.ops.pallas.paged_attention import fused_paged_attention

    rng = np.random.RandomState(0)
    failures = []

    def pin(tag, got, want):
        if np.asarray(got).tobytes() != np.asarray(want).tobytes():
            d = np.max(np.abs(np.asarray(got, np.float64)
                              - np.asarray(want, np.float64)))
            failures.append(f"decode parity [{tag}]: maxdiff {d}")

    for bs, q, kp, vp, pt, pos in _decode_cases(rng):
        for layer in (0, 1):
            want = paged_attend(q, kp, vp, pt, pos, bs, layer=layer)
            got = fused_paged_attention(q, kp, vp, pt, pos, block_size=bs,
                                        layer=layer)
            pin(f"f32 bs={bs} layer={layer}", got, want)
        # bounded walk: any sufficient hint is bit-neutral
        hint = int(pos.max()) // bs + 1
        pin(f"f32 bs={bs} max_blocks={hint}",
            fused_paged_attention(q, kp, vp, pt, pos, block_size=bs,
                                  max_blocks=hint),
            paged_attend(q, kp, vp, pt, pos, bs, max_blocks=hint))
        # bf16 pools
        kb, vb = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
        qb = q.astype(jnp.bfloat16)
        pin(f"bf16 bs={bs}",
            fused_paged_attention(qb, kb, vb, pt, pos, block_size=bs),
            paged_attend(qb, kb, vb, pt, pos, bs))
        # int8-KV arm (folded-dequant contract on both sides)
        ki = np.asarray(quantize_kv(kp, 8.0))
        vi = np.asarray(quantize_kv(vp, 8.0))
        pin(f"int8 bs={bs}",
            fused_paged_attention(q, ki, vi, pt, pos, block_size=bs,
                                  kv_scale=8.0),
            paged_attend(q, ki, vi, pt, pos, bs, kv_scale=8.0))
    return failures


def check_opt_parity() -> list:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import optimizer_ops  # noqa: F401 (registers)
    from paddle_tpu.ops import registry
    from paddle_tpu.ops.pallas.zero_update import fused_flat_update

    rng = np.random.RandomState(1)
    failures = []
    for op_type in ("sgd", "momentum", "adam", "adamw"):
        for shape in ((256,), (3, 128)):
            p = rng.randn(*shape).astype(np.float32)
            g = rng.randn(*shape).astype(np.float32)
            lr = np.asarray([1e-3], np.float32)
            ins = {"Param": [p], "Grad": [g], "LearningRate": [lr]}
            attrs = {}
            if op_type == "momentum":
                ins["Velocity"] = [rng.randn(*shape).astype(np.float32)]
                attrs = {"mu": 0.9, "use_nesterov": True,
                         "regularization_method": "l2_decay",
                         "regularization_coeff": 1e-4}
            elif op_type in ("adam", "adamw"):
                ins["Moment1"] = [rng.randn(*shape).astype(np.float32)]
                ins["Moment2"] = [np.abs(rng.randn(*shape))
                                  .astype(np.float32)]
                ins["Beta1Pow"] = [np.asarray([0.9 ** 3], np.float32)]
                ins["Beta2Pow"] = [np.asarray([0.999 ** 3], np.float32)]

            # the oracle is the JITTED rule — __zero_update__ always runs
            # inside the compiled train step, and XLA's fusion rounding
            # is part of the contract the kernel reproduces
            def rule(ins=ins, attrs=attrs, op_type=op_type):
                return registry.get(op_type).lower(None, ins, attrs)
            want = jax.jit(rule)()
            got = jax.jit(lambda: fused_flat_update(op_type, ins, attrs))()
            for k in sorted(want):
                w, f = np.asarray(want[k][0]), np.asarray(got[k][0])
                if w.tobytes() != f.tobytes():
                    failures.append(
                        f"opt parity [{op_type} {shape} {k}]: maxdiff "
                        f"{np.max(np.abs(w.astype(np.float64) - f.astype(np.float64)))}")
    return failures


def check_engine_census() -> list:
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.gpt import GPTConfig, build_lm_program
    from paddle_tpu.models.gpt_decode import params_from_scope
    from paddle_tpu.serving import DecodeEngine, Request
    from paddle_tpu.serving import audit
    from paddle_tpu.testing import reset_programs

    reset_programs(seed=0)
    cfg = GPTConfig.tiny()
    cfg.max_position = 64
    build_lm_program(cfg)
    fluid.Executor().run(fluid.default_startup_program())
    params = params_from_scope(cfg)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, cfg.vocab_size, (6,)) for _ in range(2)]

    failures = []
    toks = {}
    for kern in (False, True):
        eng = DecodeEngine(params, cfg, max_slots=2, block_size=8,
                           num_blocks=16, max_len=32, window=4,
                           decode_kernel=kern)
        try:
            row = audit.decode_gather_census(eng)
            if kern and row["dense_gathers"]:
                failures.append(
                    "kernel-on window program still materializes dense "
                    f"cache views: {row['dense_gather_findings'][:3]}")
            if not kern:
                if not row["dense_gathers"]:
                    failures.append("fallback census found no dense "
                                    "gathers (census regressed)")
                audit.assert_zero_kv_copies(eng)
            comps = eng.generate(
                [Request(prompt=pr, max_new_tokens=5) for pr in prompts],
                timeout=240)
            toks[kern] = [list(c.tokens) for c in comps]
        finally:
            eng.stop()
    if toks.get(True) != toks.get(False):
        failures.append(f"engine tokens kernel on/off diverge: {toks}")
    return failures


def main() -> int:
    from paddle_tpu.testing import run_as_cpu_tool
    run_as_cpu_tool(1, os.path.abspath(__file__), sys.argv[1:])

    failures = []
    failures += check_decode_parity()
    failures += check_opt_parity()
    failures += check_engine_census()
    print("kernel smoke: decode parity (f32/bf16/int8 x block sizes + "
          "bounded walk), optimizer parity (4 ops x 2 layouts), "
          f"census + engine on/off parity — {len(failures)} failures")
    for f in failures:
        print(f"FAIL: {f}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
