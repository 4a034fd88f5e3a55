"""Inference engine: AnalysisConfig + AnalysisPredictor
(parity: paddle/fluid/inference/api/analysis_predictor.h:47,
paddle_analysis_config.h, paddle_api.h PaddleTensor/ZeroCopyTensor).

The reference's analysis pipeline (ir passes, TensorRT subgraphs, params
sync) collapses on TPU into: prune to the feed→fetch slice (already done by
save_inference_model), hold params in a private Scope, and let the
block-compiling Executor stage the whole program into ONE cached XLA
executable — the "engine op" is the entire program.  Clones share the scope
(reference: AnalysisPredictor::Clone shares params the same way).
"""

import numpy as np

from . import io as _io
from .core.executor import Executor, scope_guard
from .core.scope import Scope
from .framework import CPUPlace, TPUPlace

__all__ = [
    "AnalysisConfig", "PaddleTensor", "ZeroCopyTensor",
    "create_paddle_predictor", "AnalysisPredictor",
]


class AnalysisConfig:
    """Mirror of paddle_analysis_config.h's commonly-used surface."""

    def __init__(self, model_dir_or_prog_file=None, params_file=None):
        # reference ctor forms (paddle_analysis_config.h): one arg = model
        # dir; two args = (prog_file, params_file)
        if params_file is None:
            self._model_dir = model_dir_or_prog_file
            self._prog_file = None
            self._params_file = None
        else:
            self._model_dir = None
            self._prog_file = model_dir_or_prog_file
            self._params_file = params_file
        self._use_tpu = True
        self._device_id = 0
        self._ir_optim = True
        self._memory_optim = True
        self._feed_fetch_ops = False
        self._cpu_math_threads = 1

    # -- model location ------------------------------------------------------
    def set_model(self, a, b=None):
        if b is None:
            self._model_dir = a
        else:
            self._prog_file, self._params_file = a, b

    def model_dir(self):
        return self._model_dir

    def prog_file(self):
        return self._prog_file

    def params_file(self):
        return self._params_file

    # -- device --------------------------------------------------------------
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        # GPU request maps to the TPU chip (the reference's CUDAPlace slot)
        self._use_tpu = True
        self._device_id = device_id

    def enable_use_tpu(self, device_id=0):
        self._use_tpu = True
        self._device_id = device_id

    def disable_gpu(self):
        self._use_tpu = False

    def use_gpu(self):
        return self._use_tpu

    def gpu_device_id(self):
        return self._device_id

    def set_cpu_math_library_num_threads(self, n):
        self._cpu_math_threads = n

    # -- optimization toggles (XLA owns these; kept for API parity) ---------
    # -- serialized executable cache ----------------------------------------
    def set_optim_cache_dir(self, path):
        """Persist compiled executables across processes (the reference's
        TensorRT SetOptimCacheDir serialized-engine cache,
        paddle_analysis_config.h): compiled XLA executables are serialized
        into `path` and re-loaded by later predictors/processes, skipping
        compilation."""
        self._optim_cache_dir = path

    def optim_cache_dir(self):
        return getattr(self, "_optim_cache_dir", None)

    def switch_ir_optim(self, flag=True):
        self._ir_optim = flag

    def ir_optim(self):
        return self._ir_optim

    def enable_memory_optim(self):
        self._memory_optim = True

    def enable_mkldnn(self):
        pass

    def switch_use_feed_fetch_ops(self, flag=True):
        self._feed_fetch_ops = flag

    def switch_specify_input_names(self, flag=True):
        pass

    def tensorrt_engine_enabled(self):
        return False


class PaddleTensor:
    """Simple named ndarray container (paddle_api.h PaddleTensor)."""

    def __init__(self, data=None, name=""):
        self.name = name
        self.data = np.asarray(data) if data is not None else None
        self.shape = tuple(self.data.shape) if data is not None else ()
        self.lod = []

    def as_ndarray(self):
        return self.data


class ZeroCopyTensor:
    """Handle onto one feed/fetch slot of a predictor
    (paddle_api.h ZeroCopyTensor): copy_from_cpu stages the next input,
    copy_to_cpu reads the last output."""

    def __init__(self, predictor, name, is_input):
        self._pred = predictor
        self._name = name
        self._is_input = is_input

    def name(self):
        return self._name

    def copy_from_cpu(self, arr):
        if not self._is_input:
            raise RuntimeError("copy_from_cpu on an output tensor")
        self._pred._staged_feed[self._name] = np.asarray(arr)

    def reshape(self, shape):
        pass  # shape comes from the staged array

    def copy_to_cpu(self):
        if self._is_input:
            raise RuntimeError("copy_to_cpu on an input tensor")
        out = self._pred._last_outputs
        if out is None:
            raise RuntimeError("run the predictor before copy_to_cpu")
        return out[self._name]


class AnalysisPredictor:
    def __init__(self, config, _shared=None):
        self._config = config
        if config.optim_cache_dir():
            # the unified two-tier cache — the same store Executor.warmup
            # and the elastic standby path use, so a serving replica
            # restores the buckets a trainer or earlier replica compiled
            # (JAX_COMPILATION_CACHE_DIR, when set, wins over this path)
            from .core import compile_cache

            compile_cache.place(str(config.optim_cache_dir()))
        if _shared is not None:
            # clone: share program + scope (shared params, reference
            # AnalysisPredictor::Clone) AND the Executor — its executable
            # cache is per-instance, so a private Executor would recompile
            # per clone; sharing it means N threaded clones hit ONE
            # compiled executable (Executor.run is thread-safe for
            # inference programs: the compiled fn is pure, params read from
            # the shared scope)
            (self._program, self._feed_names, self._fetch_vars, self._scope,
             self._exe) = _shared
        else:
            place = TPUPlace(config.gpu_device_id()) if config.use_gpu() \
                else CPUPlace()
            self._exe = Executor(place)
            import os

            self._scope = Scope()
            dirname = config.model_dir()
            model_filename = params_filename = None
            if dirname is None:
                # two-file form: both files must live in one directory (the
                # save_inference_model layout)
                prog, params = config.prog_file(), config.params_file()
                if not prog:
                    raise ValueError(
                        "AnalysisConfig: set_model(dir) or "
                        "set_model(prog_file, params_file) is required")
                dirname = os.path.dirname(prog) or "."
                if (os.path.dirname(params) or ".") != dirname:
                    raise ValueError(
                        "prog_file and params_file must be in the same "
                        "directory (got %r / %r)" % (prog, params))
                model_filename = os.path.basename(prog)
                params_filename = os.path.basename(params)
            with scope_guard(self._scope):
                self._program, self._feed_names, self._fetch_vars = \
                    _io.load_inference_model(dirname, self._exe,
                                             model_filename, params_filename)
            if config.ir_optim():
                # analysis pass pipeline (analysis_predictor.cc:461
                # OptimizeInferenceProgram): graph-rewriting passes whose
                # wins XLA can't recover (they rewrite parameter values /
                # delete stateful ops); everything else is XLA's job
                from . import ir as _ir

                # fetch targets have no op consumers after load (feed/fetch
                # ops are stripped) — protect them from fusion swallowing
                protected = set(self._feed_names) | {
                    v.name for v in self._fetch_vars}
                for pname in ("delete_dropout_pass", "conv_bn_fuse_pass",
                              "multihead_matmul_fuse_pass",
                              "fc_fuse_pass", "repeated_fc_relu_fuse_pass",
                              "seqpool_concat_fuse_pass",
                              "fuse_elewise_add_act_pass"):
                    _ir.apply_pass(pname, self._program, self._scope,
                                   protected=protected)
        self._fetch_names = [v.name for v in self._fetch_vars]
        self._staged_feed = {}
        self._last_outputs = None

    # -- PaddleTensor API ----------------------------------------------------
    def run(self, inputs):
        """inputs: list[PaddleTensor] in get_input_names() order (or named).
        Returns list[PaddleTensor]."""
        feed = {}
        for i, t in enumerate(inputs):
            name = t.name or self._feed_names[i]
            feed[name] = t.data
        outs = self._run_feed(feed)
        return [PaddleTensor(outs[n], name=n) for n in self._fetch_names]

    # -- ZeroCopy API --------------------------------------------------------
    def get_input_names(self):
        return list(self._feed_names)

    def get_output_names(self):
        return list(self._fetch_names)

    def get_input_tensor(self, name):
        if name not in self._feed_names:
            raise KeyError(name)
        return ZeroCopyTensor(self, name, True)

    def get_output_tensor(self, name):
        if name not in self._fetch_names:
            raise KeyError(name)
        return ZeroCopyTensor(self, name, False)

    def zero_copy_run(self):
        missing = [n for n in self._feed_names if n not in self._staged_feed]
        if missing:
            raise RuntimeError("inputs not staged: %s" % missing)
        self._last_outputs = self._run_feed(dict(self._staged_feed))

    # -- internals -----------------------------------------------------------
    def _run_feed(self, feed):
        with scope_guard(self._scope):
            vals = self._exe.run(self._program, feed=feed,
                                 fetch_list=self._fetch_vars)
        return dict(zip(self._fetch_names, [np.asarray(v) for v in vals]))

    def clone(self):
        return AnalysisPredictor(
            self._config,
            _shared=(self._program, self._feed_names, self._fetch_vars,
                     self._scope, self._exe))

    def program(self):
        return self._program


def create_paddle_predictor(config):
    """Factory (paddle_api.h CreatePaddlePredictor)."""
    return AnalysisPredictor(config)
