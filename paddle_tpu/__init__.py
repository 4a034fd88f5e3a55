"""paddle_tpu: a TPU-native framework with the Fluid capability surface.

Usage mirrors the reference (``import paddle.fluid as fluid`` becomes
``import paddle_tpu as fluid``): build a Program with layers, run it with an
Executor on CPUPlace/TPUPlace.  Execution lowers whole blocks to XLA via JAX.
"""

import time as _time

# `import paddle_tpu`, first line to last: the ``setup.import`` span
# (core/tracing.py ``imported``), which no flag can cover
_import_started = (_time.time(), _time.perf_counter())

import jax as _jax

# Make every in-trace random draw a pure function of (key, global element
# offset): the legacy threefry lowering re-derives its counter per SHARD
# under GSPMD, so the same program draws a different dropout mask once a
# mesh shards its operands (the dp4xtp2 ~0.5%-rel drift).  Global-offset
# counters make the draw sharding-invariant.
_jax.config.update("jax_threefry_partitionable", True)

from . import framework
from .framework import (
    CPUPlace,
    CUDAPinnedPlace,
    CUDAPlace,
    Program,
    TPUPlace,
    Variable,
    cpu_places,
    cuda_places,
    default_main_program,
    default_startup_program,
    in_dygraph_mode,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
    name_scope,
    program_guard,
    tpu_places,
    require_version,
    load_op_library,
    core,
)
from . import distribute_lookup_table
from .core.scope import LoDTensorArray
from .core.executor import Executor, global_scope, scope_guard
from .core.scope import Scope
from .compiler import BuildStrategy, CompiledProgram, ExecutionStrategy
from .param_attr import ParamAttr, WeightNormParamAttr
from .backward import append_backward, gradients
from . import layers
from . import nets
from . import input
from .input import one_hot, embedding
from . import lod_tensor
from .lod_tensor import create_lod_tensor, create_random_int_lodtensor
from . import average
from . import evaluator
from . import install_check
from . import debugger
from . import parallel_executor
from .parallel_executor import ParallelExecutor
from . import initializer
from . import optimizer
from . import regularizer
from . import clip
from . import backward
from . import contrib
from . import transpiler
from .transpiler import DistributeTranspiler, DistributeTranspilerConfig
from . import incubate
from . import distributed
from . import unique_name_compat as unique_name  # noqa: F401
from .data_feeder import DataFeeder
from . import io
from .io import save_inference_model, load_inference_model
from .io import save, load, load_program_state, set_program_state
from .reader import DataLoader, PyReader
from .dataset import DatasetFactory
from . import dataset
from . import datasets
from . import dygraph
from . import metrics
from . import profiler
from .core import telemetry
from .core import tracing
from . import flags
from . import parallel
from .flags import set_flags, get_flags
from . import inference
from .inference import AnalysisConfig, create_paddle_predictor
from . import reader  # DataLoader module; also re-exports the decorators
from .reader_decorator import batch
from .core.scope import TpuTensor as LoDTensor  # reference core.LoDTensor
from . import compat_modules as _compat_modules
_compat_modules.wire_aliases()

__version__ = "0.1.0"


def data(name, shape, dtype="float32", lod_level=0):
    """fluid.data — batch dim must be given explicitly (often -1)."""
    return layers.data(
        name=name, shape=shape, dtype=dtype, lod_level=lod_level,
        append_batch_size=False,
    )


class DataFeedDesc:
    """Parsed data-feed description (reference data_feed.proto +
    python/paddle/fluid/data_feed_desc.py).  Reads the prototxt slot config
    the reference uses (name/type/dense flags under multi_slot_desc) into a
    plain object the Dataset facade consumes."""

    def __init__(self, proto_file=None):
        self.proto_file = proto_file
        self.batch_size = 32
        self.pipe_command = "cat"
        self.slots = []  # [{"name","type","is_dense","is_used"}]
        if proto_file:
            self._parse(proto_file)

    def _parse(self, path):
        import re

        text = open(path).read()
        self.slots = []
        for m in re.finditer(r"slots\s*\{([^}]*)\}", text):
            body = m.group(1)

            def field(key, default=None):
                fm = re.search(r"%s\s*:\s*(\S+)" % key, body)
                return fm.group(1).strip('"') if fm else default

            self.slots.append({
                "name": field("name", ""),
                "type": field("type", "float"),
                "is_dense": field("is_dense", "false") == "true",
                "is_used": field("is_used", "false") == "true",
            })
        bm = re.search(r"batch_size\s*:\s*(\d+)", text)
        if bm:
            self.batch_size = int(bm.group(1))

    def set_batch_size(self, bs):
        self.batch_size = int(bs)

    def set_dense_slots(self, names):
        for s in self.slots:
            if s["name"] in names:
                s["is_dense"] = True

    def set_use_slots(self, names):
        for s in self.slots:
            if s["name"] in names:
                s["is_used"] = True

    def desc(self):
        lines = ["batch_size: %d" % self.batch_size,
                 'pipe_command: "%s"' % self.pipe_command,
                 "multi_slot_desc {"]
        for s in self.slots:
            lines += ["  slots {",
                      '    name: "%s"' % s["name"],
                      '    type: "%s"' % s["type"],
                      "    is_dense: %s" % str(s["is_dense"]).lower(),
                      "    is_used: %s" % str(s["is_used"]).lower(),
                      "  }"]
        lines.append("}")
        return "\n".join(lines)


tracing.imported(_import_started[0],
                 (_time.perf_counter() - _import_started[1]) * 1e3)
