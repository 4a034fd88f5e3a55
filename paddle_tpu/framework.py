"""Graph IR: Program / Block / Operator / Variable.

TPU-native re-design of the reference's static-graph IR
(``paddle/fluid/framework/framework.proto`` + ``python/paddle/fluid/framework.py``,
Variable at framework.py:561, Operator at :1680, Block at :2132, Program at :3515).

Unlike the reference there is no protobuf/C++ desc split: the Python objects ARE
the IR, and execution lowers whole blocks into a single jitted XLA computation
(see ``paddle_tpu.core.executor``).  Serialization is JSON (see ``to_dict``).
"""

import contextlib
import itertools
import threading
import copy
import json

import numpy as np

from .utils import unique_name

__all__ = [
    "Program",
    "Block",
    "Operator",
    "Variable",
    "Parameter",
    "default_main_program",
    "default_startup_program",
    "program_guard",
    "name_scope",
    "cpu_places",
    "in_dygraph_mode",
    "convert_np_dtype_to_dtype_",
    "core",
]

# ---------------------------------------------------------------------------
# dtypes — canonical form is a numpy dtype string ('float32', ...), with
# 'bfloat16' handled specially (jax.numpy dtype).
# ---------------------------------------------------------------------------

_SUPPORTED_DTYPES = (
    "bool",
    "int8",
    "uint8",
    "int16",
    "int32",
    "int64",
    "float16",
    "bfloat16",
    "float32",
    "float64",
)


def convert_np_dtype_to_dtype_(dtype):
    """Normalize a dtype spec (numpy dtype / str / jnp dtype) to a str name."""
    if dtype is None:
        return None
    name = getattr(dtype, "name", None)
    if name is None:
        if isinstance(dtype, str):
            name = dtype
        else:
            name = np.dtype(dtype).name
    if name == "bfloat16" or "bfloat16" in str(dtype):
        return "bfloat16"
    if name not in _SUPPORTED_DTYPES:
        raise TypeError("unsupported dtype: %r" % (dtype,))
    return name


def dtype_to_np(dtype):
    if dtype == "bfloat16":
        import jax.numpy as jnp

        return jnp.bfloat16
    return np.dtype(dtype)


# ---------------------------------------------------------------------------
# Var types (subset of framework.proto VarType, framework.proto:105)
# ---------------------------------------------------------------------------


class VarTypes:
    LOD_TENSOR = "lod_tensor"
    SELECTED_ROWS = "selected_rows"
    LOD_TENSOR_ARRAY = "lod_tensor_array"
    READER = "reader"
    STEP_SCOPES = "step_scopes"
    RAW = "raw"


# Op role annotation protocol (reference: op_proto_maker.h:26-48).  Backward
# and the distributed transpilers key off these.
class OpRole:
    Forward = 0
    Backward = 1
    Optimize = 2
    RPC = 3
    Dist = 4
    LRSched = 16
    Loss = 256

OP_ROLE_KEY = "op_role"
OP_ROLE_VAR_KEY = "op_role_var"


# ---------------------------------------------------------------------------
# Places. TPUPlace is the native device; CUDAPlace is provided as a
# compatibility alias so `fluid.CUDAPlace -> fluid.TPUPlace` swaps are the
# only user-visible change (reference: platform/place.h:26-79).
# ---------------------------------------------------------------------------


class Place:
    def __init__(self, device_id=0):
        self.device_id = int(device_id)

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self.device_id)

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def jax_device(self):
        """The one device this place names, or an error — never a stand-in.

        CPUPlace is the host backend's device.  TPUPlace(i) is the i-th
        accelerator this process addresses; with no accelerator it raises,
        except where the process was pinned to the CPU on purpose
        (``JAX_PLATFORMS=cpu``: the test tier's virtual mesh stands in for
        the chips, one CPU device each).  An id past the device count is an
        error, not chip ``id % count``."""
        import jax

        # process-LOCAL devices: under multi-controller jax (nccl2-mode
        # analog) eager values and single-device programs must live on a
        # device this process addresses, never on another host's
        if isinstance(self, CPUPlace):
            devs = jax.local_devices(backend="cpu")
        else:
            devs = [d for d in jax.local_devices() if d.platform != "cpu"]
            if not devs:
                if (jax.config.jax_platforms or "").split(",")[0] != "cpu":
                    raise RuntimeError(
                        "%r: JAX found no accelerator (devices: %s).  Run "
                        "on a chip, use CPUPlace(), or pin the CPU on "
                        "purpose with JAX_PLATFORMS=cpu."
                        % (self, jax.local_devices()))
                devs = jax.local_devices()
        if not 0 <= self.device_id < len(devs):
            raise RuntimeError("%r: this process has %d such device(s)"
                               % (self, len(devs)))
        return devs[self.device_id]


class CPUPlace(Place):
    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    pass


class CUDAPlace(TPUPlace):
    """Compatibility alias: maps to the TPU device."""


class CUDAPinnedPlace(CPUPlace):
    pass


def cpu_places(device_count=None):
    return [CPUPlace()]


def tpu_places(device_ids=None):
    import jax

    n = len(jax.devices())
    ids = range(n) if device_ids is None else device_ids
    return [TPUPlace(i) for i in ids]


cuda_places = tpu_places


def is_compiled_with_cuda():
    return False


def is_compiled_with_tpu():
    return True


def require_version(min_version, max_version=None):
    """Raise unless the installed version is within [min_version,
    max_version] (reference framework.py:66).  Version strings are 1-4
    dot-separated integers; missing components compare as 0."""
    import re as _re

    if not isinstance(min_version, str):
        raise TypeError(
            "The type of 'min_version' in require_version must be str, but "
            "received %s." % type(min_version))
    if not isinstance(max_version, (str, type(None))):
        raise TypeError(
            "The type of 'max_version' in require_version must be str or "
            "type(None), but received %s." % type(max_version))

    def parse(ver, arg):
        m = _re.match(r"\d+(\.\d+){0,3}", ver)
        if m is None or m.group() != ver:
            raise ValueError(
                "The value of '%s' in require_version must be in format "
                "'\\d+(\\.\\d+){0,3}', like '1.5.2.0', but received %s"
                % (arg, ver))
        parts = [int(p) for p in ver.split(".")]
        return parts + [0] * (4 - len(parts))

    lo = parse(min_version, "min_version")
    hi = parse(max_version, "max_version") if max_version is not None else None
    from . import __version__ as _v

    m = _re.match(r"\d+(\.\d+){0,3}", _v)
    if m is None:
        # dev/rc build with no leading numeric component: reference warns
        # and accepts rather than blaming the caller's argument
        import warnings

        warnings.warn(
            "paddle_tpu version %s or higher is required, but a development "
            "version (%s) is installed; please make sure the version is "
            "good with your code." % (min_version, _v))
        return
    parts = [int(p) for p in m.group().split(".")]
    installed = parts + [0] * (4 - len(parts))
    if installed < lo or (hi is not None and installed > hi):
        raise Exception(
            "VersionError: paddle_tpu version %s does not satisfy the "
            "requirement [%s, %s]" % (_v, min_version, max_version or "any"))


def load_op_library(lib_filename):
    """Reference framework.py:4772 loads a .so of custom C++ OpKernels and
    refreshes the proto registry.  TPU custom ops are Python/Pallas
    lowerings registered through core.registry.register_op instead; a
    shared library of CUDA kernels cannot be mapped onto the XLA path, so
    this raises with the supported alternative spelled out."""
    raise NotImplementedError(
        "load_op_library(%r): custom ops on the TPU backend are added with "
        "paddle_tpu.core.registry.register_op (a JAX/Pallas lowering), not "
        "a dynamic library of CUDA kernels" % (lib_filename,))


# ---------------------------------------------------------------------------
# Variable
# ---------------------------------------------------------------------------


class Variable:
    """A node in a Block's symbol table (reference framework.py:561).

    Holds static metadata only (shape may contain -1 for the batch dim);
    values live in a Scope at run time.
    """

    def __init__(
        self,
        block,
        name=None,
        shape=None,
        dtype=None,
        lod_level=0,
        persistable=False,
        stop_gradient=False,
        type=VarTypes.LOD_TENSOR,
        is_data=False,
        need_check_feed=False,
        initializer=None,
        **kwargs,
    ):
        self.block = block
        if name is None:
            name = unique_name.generate("_generated_var")
        self.name = name
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = convert_np_dtype_to_dtype_(dtype) if dtype is not None else None
        self.lod_level = lod_level
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.type = type
        self.is_data = is_data
        self.need_check_feed = need_check_feed
        # Optional jax.sharding.PartitionSpec-like annotation (tuple of axis
        # names / None) consumed by the executor for TP/DP layouts.
        self.sharding = kwargs.get("sharding", None)
        self.initializer = initializer
        # dygraph (eager) mode: concrete jax.Array value + accumulated grad
        # (analog of imperative::VarBase, paddle/fluid/imperative/layer.h:55)
        self._ivar = None
        self._grad_ivar = None

    # -- api parity helpers --------------------------------------------------
    def numpy(self, scope=None):
        from .core.executor import global_scope

        if self._ivar is not None:
            return np.asarray(self._ivar)
        scope = scope or global_scope()
        var = scope.find_var(self.name)
        if var is None:
            raise RuntimeError("variable %s has no value in scope" % self.name)
        return np.asarray(var.get_tensor())

    def set_value(self, value, scope=None):
        from .core.executor import global_scope

        scope = scope or global_scope()
        scope.var(self.name).set(value)

    @property
    def grad_name(self):
        return _grad_var_name(self.name)

    # -- dygraph autograd ----------------------------------------------------
    def backward(self, backward_strategy=None, retain_graph=False):
        if not in_dygraph_mode():
            raise RuntimeError(
                "Variable.backward() only works in dygraph mode; use "
                "append_backward/Optimizer.minimize for static graphs"
            )
        from .dygraph import engine

        engine.run_backward(_dygraph_tracer(), self, retain_graph=retain_graph)

    def gradient(self):
        if self._grad_ivar is None:
            return None
        return np.asarray(self._grad_ivar)

    def clear_gradient(self):
        self._grad_ivar = None

    def astype(self, dtype):
        from . import layers

        return layers.cast(self, dtype)

    def __repr__(self):
        return "Variable(name=%s, shape=%s, dtype=%s%s)" % (
            self.name,
            self.shape,
            self.dtype,
            ", persistable" if self.persistable else "",
        )

    __str__ = __repr__

    def to_dict(self):
        return {
            "name": self.name,
            "shape": list(self.shape) if self.shape is not None else None,
            "dtype": self.dtype,
            "lod_level": self.lod_level,
            "persistable": self.persistable,
            "stop_gradient": self.stop_gradient,
            "type": self.type,
            "is_data": self.is_data,
            "is_parameter": isinstance(self, Parameter),
            "trainable": getattr(self, "trainable", None),
            "sharding": list(self.sharding) if self.sharding else None,
        }


GRAD_SUFFIX = "@GRAD"


def _grad_var_name(name):
    return name + GRAD_SUFFIX


class Parameter(Variable):
    """A trainable persistable variable (reference framework.py:5157)."""

    def __init__(self, block, shape, dtype, **kwargs):
        kwargs.setdefault("persistable", True)
        self.trainable = kwargs.pop("trainable", True)
        self.optimize_attr = kwargs.pop("optimize_attr", {"learning_rate": 1.0})
        self.regularizer = kwargs.pop("regularizer", None)
        self.gradient_clip_attr = kwargs.pop("gradient_clip_attr", None)
        self.do_model_average = kwargs.pop("do_model_average", None)
        self.is_distributed = kwargs.pop("is_distributed", False)
        super().__init__(block, shape=shape, dtype=dtype, **kwargs)


# ---------------------------------------------------------------------------
# Operator
# ---------------------------------------------------------------------------


def _as_varname_list(block, v):
    """Normalize an input/output slot value to a list of var names."""
    if v is None:
        return []
    if isinstance(v, (list, tuple)):
        return [_single_varname(block, x) for x in v]
    return [_single_varname(block, v)]


def _single_varname(block, v):
    if isinstance(v, Variable):
        return v.name
    if isinstance(v, str):
        return v
    raise TypeError("expected Variable or str, got %r" % (v,))


class Operator:
    """One op in a block (reference framework.py:1680).

    inputs/outputs map slot name -> list of variable names. attrs is a plain
    dict (JSON-serializable values only).
    """

    def __init__(self, block, type, inputs=None, outputs=None, attrs=None):
        self.block = block
        self.type = type
        self.inputs = {
            k: _as_varname_list(block, v) for k, v in (inputs or {}).items()
        }
        self.outputs = {
            k: _as_varname_list(block, v) for k, v in (outputs or {}).items()
        }
        self.attrs = dict(attrs or {})
        self.attrs.setdefault(OP_ROLE_KEY, _current_role())

    def input(self, slot):
        return self.inputs.get(slot, [])

    def output(self, slot):
        return self.outputs.get(slot, [])

    @property
    def input_arg_names(self):
        return [n for names in self.inputs.values() for n in names]

    @property
    def output_arg_names(self):
        return [n for names in self.outputs.values() for n in names]

    def attr(self, name):
        return self.attrs.get(name)

    def _set_attr(self, name, val):
        self.attrs[name] = val

    def has_attr(self, name):
        return name in self.attrs

    def __repr__(self):
        return "{%s: inputs=%s outputs=%s}" % (self.type, self.inputs, self.outputs)

    def to_dict(self):
        attrs = {}
        for k, v in self.attrs.items():
            if isinstance(v, np.ndarray):
                v = v.tolist()
            if isinstance(v, (np.integer,)):
                v = int(v)
            if isinstance(v, (np.floating,)):
                v = float(v)
            attrs[k] = v
        return {
            "type": self.type,
            "inputs": self.inputs,
            "outputs": self.outputs,
            "attrs": attrs,
        }


# ---------------------------------------------------------------------------
# Block
# ---------------------------------------------------------------------------


class Block:
    def __init__(self, program, idx, parent_idx=-1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars = {}  # name -> Variable
        self.ops = []  # list[Operator]

    @property
    def parent_block(self):
        if self.parent_idx < 0:
            return None
        return self.program.block(self.parent_idx)

    # -- vars ---------------------------------------------------------------
    def create_var(self, **kwargs):
        name = kwargs.get("name")
        if name is not None and name in self.vars:
            return self.vars[name]
        var = Variable(self, **kwargs)
        self.vars[var.name] = var
        return var

    def create_parameter(self, **kwargs):
        shape = kwargs.pop("shape")
        dtype = kwargs.pop("dtype")
        param = Parameter(self, shape, dtype, **kwargs)
        # parameters always live in the top-level (global) block's symbol table
        gblock = self.program.global_block()
        gblock.vars[param.name] = param
        if self is not gblock:
            self.vars[param.name] = param
        if in_dygraph_mode():
            param.stop_gradient = not param.trainable
            _dygraph_tracer().track_parameter(param)
        return param

    def var(self, name):
        v = self._find_var_recursive(name)
        if v is None:
            raise ValueError("variable %r not found in block %d" % (name, self.idx))
        return v

    def has_var(self, name):
        return name in self.vars

    def has_var_recursive(self, name):
        return self._find_var_recursive(name) is not None

    def _find_var_recursive(self, name):
        blk = self
        while blk is not None:
            if name in blk.vars:
                return blk.vars[name]
            blk = blk.parent_block
        return None

    def all_parameters(self):
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    # -- ops ----------------------------------------------------------------
    def append_op(self, type, inputs=None, outputs=None, attrs=None):
        from .core.registry import get_op_def

        if in_dygraph_mode():
            # eager dispatch: execute the op's lowering immediately; no op is
            # appended to the block (tracer.cc:82 TraceOp analog)
            return _dygraph_tracer().trace_op(self, type, inputs, outputs,
                                              attrs)
        op = Operator(self, type, inputs, outputs, attrs)
        opdef = get_op_def(type)  # raises for unknown op types
        if opdef is not None:
            opdef.validate(op)
        self.ops.append(op)
        self.program._bump_version()
        # static shape/dtype inference for outputs lacking metadata
        if opdef is not None:
            opdef.run_infer_shape(op, self)
        return op

    def _insert_op(self, index, type, inputs=None, outputs=None, attrs=None):
        from .core.registry import get_op_def

        op = Operator(self, type, inputs, outputs, attrs)
        opdef = get_op_def(type)
        if opdef is not None:
            opdef.validate(op)
        self.ops.insert(index, op)
        self.program._bump_version()
        if opdef is not None:
            opdef.run_infer_shape(op, self)
        return op

    def _remove_op(self, index):
        del self.ops[index]
        self.program._bump_version()

    def prepend_op(self, **kwargs):
        return self._insert_op(0, **kwargs)

    def __repr__(self):
        lines = ["Block %d (parent %d):" % (self.idx, self.parent_idx)]
        for v in self.vars.values():
            lines.append("  " + repr(v))
        for op in self.ops:
            lines.append("  " + repr(op))
        return "\n".join(lines)

    def to_dict(self):
        return {
            "idx": self.idx,
            "parent_idx": self.parent_idx,
            "vars": [v.to_dict() for v in self.vars.values()],
            "ops": [op.to_dict() for op in self.ops],
        }


# ---------------------------------------------------------------------------
# Program
# ---------------------------------------------------------------------------


class Program:
    """A whole model: list of blocks, block 0 is global (reference framework.py:3515)."""

    _uid_counter = itertools.count()

    def __init__(self):
        # monotonic process-wide UID: executor caches key on this instead of
        # id(program), which a GC'd Program's successor can alias
        self._uid = next(Program._uid_counter)
        self.blocks = [Block(self, 0)]
        self.current_block_idx = 0
        self.random_seed = 0
        self._version = 0
        self._op_role = OpRole.Forward
        self._op_role_var = []
        # executor hints
        self._is_test = False
        self._sharding_mesh = None
        # non-iterable DataLoaders attached to this program (reader.py):
        # exe.run(feed=None) pulls batches from the first started one
        self._attached_loaders = []

    # -- version (invalidates executor caches) ------------------------------
    def _bump_version(self):
        self._version += 1

    @property
    def version(self):
        return self._version

    # -- blocks -------------------------------------------------------------
    def global_block(self):
        return self.blocks[0]

    def block(self, idx):
        return self.blocks[idx]

    def current_block(self):
        return self.blocks[self.current_block_idx]

    def _create_block(self, parent_idx=None):
        parent = self.current_block_idx if parent_idx is None else parent_idx
        blk = Block(self, len(self.blocks), parent)
        self.blocks.append(blk)
        self.current_block_idx = blk.idx
        self._bump_version()
        return blk

    def _rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    # -- op role protocol ----------------------------------------------------
    @contextlib.contextmanager
    def _optimized_guard(self, param_and_grads):
        old_role, old_var = self._op_role, self._op_role_var
        self._op_role = OpRole.Optimize
        self._op_role_var = [
            v.name if isinstance(v, Variable) else v for v in param_and_grads
        ]
        try:
            yield
        finally:
            self._op_role, self._op_role_var = old_role, old_var

    @contextlib.contextmanager
    def _backward_role_guard(self):
        old_role = self._op_role
        self._op_role = OpRole.Backward
        try:
            yield
        finally:
            self._op_role = old_role

    @contextlib.contextmanager
    def _lr_schedule_guard(self):
        old_role = self._op_role
        self._op_role = OpRole.LRSched
        try:
            yield
        finally:
            self._op_role = old_role

    # -- cloning / pruning ---------------------------------------------------
    def clone(self, for_test=False):
        p = Program()
        p.blocks = []
        for blk in self.blocks:
            nb = Block(p, blk.idx, blk.parent_idx)
            for name, v in blk.vars.items():
                if isinstance(v, Parameter):
                    nv = Parameter(
                        nb,
                        v.shape,
                        v.dtype,
                        name=v.name,
                        trainable=v.trainable,
                        regularizer=v.regularizer,
                        optimize_attr=dict(v.optimize_attr),
                        stop_gradient=v.stop_gradient,
                        initializer=v.initializer,
                        sharding=v.sharding,
                    )
                else:
                    nv = Variable(
                        nb,
                        name=v.name,
                        shape=v.shape,
                        dtype=v.dtype,
                        lod_level=v.lod_level,
                        persistable=v.persistable,
                        stop_gradient=v.stop_gradient,
                        type=v.type,
                        is_data=v.is_data,
                        initializer=v.initializer,
                        sharding=v.sharding,
                    )
                nb.vars[name] = nv
            for op in blk.ops:
                nop = Operator(
                    nb,
                    op.type,
                    {k: list(v) for k, v in op.inputs.items()},
                    {k: list(v) for k, v in op.outputs.items()},
                    copy.deepcopy(op.attrs),
                )
                nb.ops.append(nop)
            p.blocks.append(nb)
        p.current_block_idx = 0
        p.random_seed = self.random_seed
        p._is_test = for_test
        if for_test:
            for blk in p.blocks:
                for op in blk.ops:
                    if "is_test" in op.attrs:
                        op.attrs["is_test"] = True
                    if op.type == "dropout":
                        op.attrs["is_test"] = True
        p._bump_version()
        return p

    def list_vars(self):
        for blk in self.blocks:
            for v in blk.vars.values():
                yield v

    # -- serialization -------------------------------------------------------
    def to_dict(self):
        return {
            "version": 1,
            "random_seed": self.random_seed,
            "blocks": [b.to_dict() for b in self.blocks],
        }

    def to_string(self, throw_on_error=False, with_details=False):
        return json.dumps(self.to_dict(), indent=1)

    __str__ = to_string

    @staticmethod
    def from_dict(d):
        p = Program()
        p.random_seed = d.get("random_seed", 0)
        p.blocks = []
        for bd in d["blocks"]:
            blk = Block(p, bd["idx"], bd["parent_idx"])
            for vd in bd["vars"]:
                cls = Parameter if vd.get("is_parameter") else Variable
                kwargs = dict(
                    name=vd["name"],
                    lod_level=vd.get("lod_level", 0),
                    persistable=vd.get("persistable", False),
                    stop_gradient=vd.get("stop_gradient", False),
                    type=vd.get("type", VarTypes.LOD_TENSOR),
                    is_data=vd.get("is_data", False),
                )
                if vd.get("sharding"):
                    kwargs["sharding"] = tuple(vd["sharding"])
                shape = tuple(vd["shape"]) if vd.get("shape") is not None else None
                if cls is Parameter:
                    v = Parameter(blk, shape, vd["dtype"], **kwargs)
                else:
                    v = Variable(blk, shape=shape, dtype=vd["dtype"], **kwargs)
                blk.vars[v.name] = v
            for od in bd["ops"]:
                op = Operator(blk, od["type"], od["inputs"], od["outputs"], od["attrs"])
                blk.ops.append(op)
            p.blocks.append(blk)
        p._bump_version()
        return p


# ---------------------------------------------------------------------------
# default programs / guards
# ---------------------------------------------------------------------------

_main_program = Program()
_startup_program = Program()

# Per-thread default-program overrides: concurrent builder threads (e.g.
# pserver/worker role threads standing in for the reference's separate
# processes, test harnesses) must not race on the global defaults.  The
# MAIN thread keeps using the module globals so that programs built in the
# main thread remain visible to helper threads that never called
# program_guard themselves (trainer feed threads, pipeline sections).
_prog_tls = threading.local()


def _is_main_thread():
    return threading.current_thread() is threading.main_thread()


def default_main_program():
    if not _is_main_thread() and getattr(_prog_tls, "main", None) is not None:
        return _prog_tls.main
    return _main_program


def default_startup_program():
    if not _is_main_thread() and getattr(_prog_tls, "startup", None) is not None:
        return _prog_tls.startup
    return _startup_program


def switch_main_program(program):
    global _main_program
    if _is_main_thread():
        old = _main_program
        _main_program = program
    else:
        old = getattr(_prog_tls, "main", None)
        _prog_tls.main = program
    return old


def switch_startup_program(program):
    global _startup_program
    if _is_main_thread():
        old = _startup_program
        _startup_program = program
    else:
        old = getattr(_prog_tls, "startup", None)
        _prog_tls.startup = program
    return old


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    old_main = switch_main_program(main_program)
    old_startup = None
    if startup_program is not None:
        old_startup = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(old_main)
        if old_startup is not None:
            switch_startup_program(old_startup)


@contextlib.contextmanager
def name_scope(prefix=None):
    # cosmetic only (reference framework.py name_scope); kept for API parity
    yield


def _current_role():
    prog = default_main_program()
    return prog._op_role if prog else OpRole.Forward


# ---------------------------------------------------------------------------
# dygraph mode switch (implemented in paddle_tpu.dygraph)
# ---------------------------------------------------------------------------

_dygraph_tracer_ = None


def in_dygraph_mode():
    return _dygraph_tracer_ is not None


def _dygraph_tracer():
    return _dygraph_tracer_


# ---------------------------------------------------------------------------
# `core` compatibility shim: a handful of symbols user code expects on
# fluid.core in the reference (pybind module).
# ---------------------------------------------------------------------------


class _CoreShim:
    CPUPlace = CPUPlace
    TPUPlace = TPUPlace
    CUDAPlace = CUDAPlace
    VarDesc = None

    @staticmethod
    def is_compiled_with_cuda():
        return False

    # NOTE: fluid.core resolves to the paddle_tpu.core package (the
    # submodule import rebinds the attribute after this shim); the pybind
    # aliases (LoDTensor, LoDTensorArray, Scope) live in core/__init__.py
    # only, so there is a single alias table.


core = _CoreShim()
