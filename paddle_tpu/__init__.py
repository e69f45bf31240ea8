"""paddle_tpu: a TPU-native deep-learning framework with PaddlePaddle-Fluid
capability parity.

Built from scratch on jax/XLA/pallas/pjit — NOT a port of the reference
(qjing666/Paddle). See SURVEY.md for the capability map and the architecture
stance: programs lower to single XLA computations; parallelism is mesh +
sharding; grads come from jax.vjp; the reference's CUDA/allocator/executor
machinery is subsumed by the XLA runtime.

Layout:
    framework/   Program IR, Executor (block -> jitted XLA), autodiff, Scope
    ops/         op registry + JAX lowerings (the ~706-op surface, growing)
    layers/      fluid.layers.* graph-building API
    nn/          paddle.nn Layer stack (dygraph-first)
    dygraph/     eager tracer + tape autograd
    tensor/      paddle.tensor functional API
    parallel/    mesh, shardings, collectives, pipeline & strategy transforms
    distributed/ fleet facade, launch, env contract
    models/      flagship model zoo (LeNet, ResNet, BERT, ERNIE, Wide&Deep)
"""
from __future__ import annotations

import sys as _sys
import time as _time

_IMPORT_T0_NS = _time.perf_counter_ns()   # the `startup.import` span's start


def _entered_with() -> dict:
    """What the caller had done before the package was entered: the
    harness imports jax and starts the backend first, a user's script
    usually neither. Args of the `startup.boot` span."""
    bridge = _sys.modules.get("jax._src.xla_bridge")
    return {"jax_imported": "jax" in _sys.modules,
            "backend_initialized": bool(
                bridge is not None and bridge.backends_are_initialized())}


_ENTERED_WITH = _entered_with()

# --- fluid-style core -------------------------------------------------------
from .framework.program import (Program, program_guard, default_main_program,
                                default_startup_program, in_dygraph_mode,
                                Variable, Parameter)
from .framework.executor import Executor
from .framework.scope import global_scope, Scope
from .framework.backward import append_backward, gradients
from .framework import unique_name
from .layer_helper import ParamAttr
from . import initializer
from . import layers
from . import optimizer
from . import regularizer
from . import clip as _clip_module  # paddle.clip (the name) is the tensor fn;
# the gradient-clip classes live at paddle.nn.ClipGradBy* and fluid.clip
from . import io

# ops must import so registrations run
from .ops import (math_ops, nn_ops, tensor_ops, optimizer_ops,  # noqa: F401
                  metric_ops, attention, sequence_ops,  # noqa: F401
                  extra_ops, decode_ops, detection_ops,  # noqa: F401
                  detection_assign_ops,  # noqa: F401
                  dense_tail_ops, dense_tail_ops2,  # noqa: F401
                  sparse_grad, moe, tail_ops, lod_ops,  # noqa: F401
                  int8_ops, fused_ce, paged_ops, llm_ops, ssm, kda,
                  sparse_index)  # noqa: F401

__version__ = "0.1.0"


# Device placeholders (reference platform/place.h) — devices are owned by the
# JAX runtime; these exist for source compatibility.
class CPUPlace:
    def __repr__(self):
        return "CPUPlace"


class CUDAPlace:
    def __init__(self, id=0):
        self.id = id


class TPUPlace:
    def __init__(self, id=0):
        self.id = id


def CUDAPinnedPlace():
    return CPUPlace()


def is_compiled_with_cuda():
    return False


def is_compiled_with_tpu():
    import jax
    try:
        return any(d.platform == "tpu" for d in jax.devices())
    except RuntimeError:
        return False


def seed(value: int):
    """paddle.seed / fluid random seed: resets the global PRNG state."""
    import jax
    default_main_program().random_seed = value
    default_startup_program().random_seed = value
    global_scope().set("__rng_state__", jax.random.key(value))


def enable_static():
    from .framework.program import _set_dygraph_tracer
    _set_dygraph_tracer(None)


def disable_static():
    from .dygraph.tracer import enable_dygraph
    enable_dygraph()


# fluid alias module-style access: paddle_tpu.fluid
from . import fluid  # noqa: E402,F401

# --- paddle 2.0-style API ---------------------------------------------------
from . import nn  # noqa: E402
from . import dygraph  # noqa: E402
from .dygraph import (Tensor, to_tensor, to_variable, no_grad, grad)  # noqa: E402
from .tensor import *  # noqa: E402,F401,F403
from . import tensor  # noqa: E402
from .tensor import __all__ as _tensor_all

static = fluid  # paddle.static namespace parity


def get_default_dtype():
    return "float32"


def set_default_dtype(d):
    pass


# --- high-level API + metrics + data (reference hapi/, metric/, io) --------
from . import metric  # noqa: E402
from .hapi import Model, Input  # noqa: E402
from . import hapi  # noqa: E402
from . import io  # noqa: E402,F401  (paddle.io.DataLoader etc.)
from . import dataset as _fluid_dataset  # noqa: E402,F401
# Legacy paddle.dataset.* reader modules live on the same `dataset`
# namespace as fluid's DatasetFactory (reference python/paddle/dataset/):
# paddle.dataset.mnist.train() and fluid.dataset.DatasetFactory() both work.
from . import dataset_legacy as _dataset_legacy  # noqa: E402


def _graft_legacy_datasets():
    for _name in _dataset_legacy.__all__:
        _mod = getattr(_dataset_legacy, _name)
        setattr(_fluid_dataset, _name, _mod)
        _sys.modules[f"{__name__}.dataset.{_name}"] = _mod


_graft_legacy_datasets()
from . import vision  # noqa: E402,F401
from . import text  # noqa: E402,F401
from . import jit  # noqa: E402
from . import inference  # noqa: E402
from . import profiler  # noqa: E402
from . import monitor  # noqa: E402
from .flags import get_flags, set_flags  # noqa: E402

# --- observability: the time before the package was entered and the import's
# own span, JAX's compile phases as spans ------------------------------------
from .observability import compile_events as _compile_events  # noqa: E402
from .observability import trace as _trace  # noqa: E402

_compile_events.install()
_created_us = _trace.process_created_us()
if _created_us is not None:       # no creation time from the OS, no span
    _trace.complete("startup.boot", int(_created_us * 1000), _IMPORT_T0_NS,
                    args=_ENTERED_WITH)
_trace.complete("startup.import", _IMPORT_T0_NS, _time.perf_counter_ns())
