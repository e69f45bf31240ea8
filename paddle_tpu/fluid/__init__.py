"""fluid-compatibility namespace: `import paddle_tpu.fluid as fluid`.

Mirrors python/paddle/fluid/__init__.py's public surface for the covered
subset so reference-style user code runs unchanged.
"""
from ..framework.program import (Program, program_guard, device_guard, name_scope,  # noqa
                                 default_main_program,
                                 default_startup_program, in_dygraph_mode,
                                 Variable, Parameter)
from ..framework.executor import Executor
from ..framework.fetch import FetchHandle
from ..framework.scope import global_scope, Scope
from ..framework.backward import append_backward, gradients
from ..framework import unique_name
from ..layer_helper import ParamAttr
from .. import initializer
from .. import layers
from .. import optimizer
from .. import regularizer
from .. import clip
from .. import io
from .. import framework
from .. import (CPUPlace, CUDAPlace, TPUPlace, is_compiled_with_cuda,
                is_compiled_with_tpu)
from .. import compiler  # noqa: F401
from ..compiler import CompiledProgram, BuildStrategy, ExecutionStrategy  # noqa: F401
from .. import debugger  # noqa: F401
from .. import contrib  # noqa: F401


class core:
    """Stand-in for the pybind core module (reference pybind/pybind.cc). The
    'native core' here is jaxlib/XLA itself."""

    from ..framework.scope import Scope, global_scope
    # typed error surface (reference pybind/exception.cc:22 binds these two;
    # the typed subclasses come from framework/errors.py)
    from ..framework.errors import EnforceNotMet, EOFException

    @staticmethod
    def get_all_op_names():
        from ..ops import registry
        return registry.all_ops()


from .. import dataset  # noqa: E402  (fluid.dataset.DatasetFactory)
from ..dataloader import DataFeeder  # noqa: E402


from ..utils.custom_op import load_op_library  # noqa: E402  (reference
# framework.py:5549 exposes fluid.load_op_library)
from ..flags import get_flags, set_flags  # noqa: E402  (fluid.set_flags)
from .. import profiler  # noqa: E402     (fluid.profiler.profiler context)


def create_lod_tensor(data, recursive_seq_lens, place=None):
    """Compatibility shim for the reference's fluid.create_lod_tensor
    (python/paddle/fluid/lod_tensor.py): ragged rows + one LoD level in,
    padded-dense + lengths out — the framework-wide ragged representation
    (docs/lod_design.md). Returns (dense [B, Tmax, ...], lengths [B]);
    feed the pair to ops that take a lengths/`length=` input."""
    import numpy as np
    data = np.asarray(data)
    assert len(recursive_seq_lens) == 1, \
        "one LoD level (docs/lod_design.md); nest higher levels yourself"
    lens = [int(v) for v in recursive_seq_lens[0]]
    assert sum(lens) == data.shape[0], \
        f"lengths {lens} do not sum to rows {data.shape[0]}"
    b = len(lens)
    tmax = max(lens) if lens else 0
    dense = np.zeros((b, tmax) + data.shape[1:], data.dtype)
    off = 0
    for i, ln in enumerate(lens):
        dense[i, :ln] = data[off:off + ln]
        off += ln
    return dense, np.asarray(lens, np.int64)


def create_random_int_lodtensor(recursive_seq_lens, base_shape, place, low,
                                high):
    """Reference fluid.create_random_int_lodtensor parity (lod_tensor.py)."""
    import numpy as np
    total = sum(int(v) for v in recursive_seq_lens[0])
    data = np.random.randint(low, high + 1,
                             (total,) + tuple(base_shape)).astype(np.int64)
    return create_lod_tensor(data, recursive_seq_lens, place)
