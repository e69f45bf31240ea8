"""What the tests of the sparse causal LMs share (`tests/test_deepseek_v3.py`,
`test_mellum.py`, `test_nemotron_h.py`, `test_nemotron3_super.py`,
`test_ling.py`, `test_causal_lm_steps.py`): the seeded batches, the program
trained from the reference's weights, the reference's own Adam steps, an op
run through its lowering, a share of `routed_moe` through a Program, the tiny
AMP step a census reads, and the digests that hold a step to the tree before.
Not collected: pytest takes `test_*.py`. Parametrised by the model module and
its reference; tolerances, fault tables and their reasons stay in the file
whose architecture they describe.
"""
import hashlib
import json
import os
import re
import sys

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.fluid as fluid  # noqa: E402
from paddle_tpu.distributed import fleet  # noqa: E402
from paddle_tpu.testing import reset_programs  # noqa: E402


def sha256(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def cut_source_lines(text: str, files: str = r"[\w/.\-]+") -> str:
    """A jaxpr's text with `<file>.py:<line>` cut; `files` a pattern for the
    files' stems (every file by default: then the path goes too)."""
    if files == r"[\w/.\-]+":
        return re.sub(r"[\w/.\-]+\.py:\d+", "F:N", text)
    return re.sub(rf"({files})\.py:\d+", r"\1.py:N", text)


def program_digest() -> str:
    """sha256 of the default main and startup Programs as built: every
    block's variables (name, shape, dtype, flags) and ops (type, slots,
    attributes) in order."""
    desc = [p.to_desc() for p in (fluid.default_main_program(),
                                  fluid.default_startup_program())]
    return sha256(json.dumps(desc, sort_keys=True, default=repr))


def tiny_step_digests(build):
    """(sha256 of a builder's tiny float32 train step of k = 2 as a jaxpr,
    source lines cut; `program_digest` of what `build` built, before the
    optimizer's ops). `build()` returns (loss, feed)."""
    reset_programs(0)
    loss, feed = build()
    built = program_digest()
    paddle.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    text = cut_source_lines(str(exe.step_jaxpr(feed, [loss], k=2)))
    return sha256(text), built


def causal_lm(module, cfg):
    """A `build` for `tiny_step_digests`: `module`'s causal LM at `cfg`."""
    def build():
        _, loss, _ = module.build_causal_lm_program(cfg)
        return loss, {"tokens": np.zeros((2, 2, cfg.seq_len), np.int64)}
    return build
