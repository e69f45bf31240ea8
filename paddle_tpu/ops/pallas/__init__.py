"""Pallas TPU kernels — the hand-written-kernel layer.

Reference counterpart: operators/math/*.cu, operators/fused/*.cu,
operators/jit/ (xbyak x86 codegen). On TPU, XLA fuses most elementwise work
already; kernels live here only where manual tiling beats the compiler —
flash attention first (HBM-bound softmax(QK^T)V).
"""

import jax


def interpret_mode() -> bool:
    """True only on the CPU backend, where the kernels run under the Pallas
    interpreter so tier-1 can pin them against their jnp oracles. On any
    accelerator backend the kernels are compiled by Mosaic — there is no
    switch that makes a TPU run interpret, so a refusal surfaces as an
    error instead of a slow pass."""
    return jax.default_backend() == "cpu"
