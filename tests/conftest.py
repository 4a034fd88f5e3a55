"""Test config: two tiers.

Default tier: JAX on a virtual 8-device CPU mesh (multi-chip sharding tests
run here; fast, deterministic, no hardware needed).

TPU tier (``PADDLE_TPU_TESTS=1 pytest -m tpu``): leaves the real accelerator
backend enabled so ``@pytest.mark.tpu`` tests exercise TPUPlace on the chip —
the per-place parametrization the reference applies through
``check_output_with_place`` (reference op_test.py:782,988).  TPU-marked tests
auto-skip in the default tier, so the plain suite stays green anywhere.
"""

import os
import sys

import pytest

TPU_TIER = os.environ.get("PADDLE_TPU_TESTS") == "1"

if not TPU_TIER:
    # before the first jax import in this process: the backend reads both
    # at initialization, and test subprocesses inherit them
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "--xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
    # tests place the compile cache in per-test tmp dirs through
    # FLAGS_compile_cache_dir; a machine-placed cache would override every
    # one of them (core/compile_cache.py) and carry state between tests
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _have_accelerator():
    return any(d.platform != "cpu" for d in jax.devices())


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tpu: needs a real TPU chip; run via PADDLE_TPU_TESTS=1 pytest -m tpu",
    )
    config.addinivalue_line(
        "markers",
        "slow: multi-process / long-running tests",
    )
    config.addinivalue_line(
        "markers",
        "flaky_ports: retries once on the free-port TOCTOU race",
    )


@pytest.fixture(autouse=True)
def _no_spans_left_for_the_next_test():
    """Spans wait in the process's buffer for a flush.  A traced test that
    never flushes (or resets) would hand its spans to whichever traced test
    its xdist worker runs next, in another file as well, and that test
    would count them: drop what is left unwritten when a test ends.  The
    package's own ``setup.import`` span waits for a process's first record
    in the same way (``tracing.imported``), and the test that happened to
    make that record would count it: a test that wants it arms it itself."""
    tracing = sys.modules.get("paddle_tpu.core.tracing")
    if tracing is not None:
        del tracing._import[:]
    yield
    tracing = sys.modules.get("paddle_tpu.core.tracing")
    if tracing is not None and tracing._unwritten:
        with tracing._lock:
            tracing._unwritten[:] = []


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    """The executor's tier-B compile cache in a directory of this test
    module's own."""
    import paddle_tpu as fluid

    d = str(tmp_path_factory.mktemp("cc"))
    old = fluid.get_flags(["FLAGS_compile_cache_dir"])
    fluid.set_flags({"FLAGS_compile_cache_dir": d})
    yield d
    fluid.set_flags(old)


@pytest.fixture()
def telemetry_on():
    """Counters and gauges on, and empty, for one test."""
    import paddle_tpu as fluid
    from paddle_tpu.core import telemetry

    fluid.set_flags({"FLAGS_telemetry": True})
    telemetry.reset()
    yield
    telemetry.reset()
    fluid.set_flags({"FLAGS_telemetry": False})


@pytest.fixture()
def interpreted(monkeypatch):
    """Pallas kernels run on the CPU, under the interpreter, and what they
    adopt is counted anew."""
    from paddle_tpu.pallas_kernels import adoption

    monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
    adoption.reset()
    yield
    adoption.reset()


def pytest_collection_modifyitems(config, items):
    if TPU_TIER and _have_accelerator():
        # inverse guard: the default-tier tests need the 8-device CPU mesh
        # this process did not configure — running them against the TPU
        # backend would exercise the wrong topology
        skip = pytest.mark.skip(
            reason="default tier needs the CPU mesh (unset PADDLE_TPU_TESTS)")
        for item in items:
            if "tpu" not in item.keywords:
                item.add_marker(skip)
        return
    if TPU_TIER:
        # neither tier can run (the CPU mesh was not configured in this
        # process either), and a chip-less "all skipped, exit 0" would read
        # as a pass of the on-chip tier
        pytest.exit("PADDLE_TPU_TESTS=1 but JAX found no accelerator; unset "
                    "it to run the CPU-mesh tier", returncode=2)
    skip = pytest.mark.skip(reason="TPU tier: set PADDLE_TPU_TESTS=1 on a "
                                   "TPU host")
    for item in items:
        if "tpu" in item.keywords:
            item.add_marker(skip)


def pytest_sessionfinish(session, exitstatus):
    """Executed-op coverage: dump and (on full default-tier runs) enforce.

    Recording happens in core/registry.py record_executed (graph run_op +
    dygraph trace_op).  Enforcement runs only for a clean, unfiltered run
    of the whole tests/ directory, so partial runs (-k, -m, single files)
    stay usable.
    """
    from paddle_tpu.core.registry import EXECUTED_OP_TYPES

    out = os.environ.get("PADDLE_TPU_OP_COVERAGE_OUT")
    if out:
        with open(out, "w") as f:
            f.write("\n".join(sorted(EXECUTED_OP_TYPES)) + "\n")
    if TPU_TIER or exitstatus != 0:
        return
    opt = session.config.option
    if (getattr(opt, "keyword", "") or getattr(opt, "markexpr", "")
            or getattr(opt, "collectonly", False)):
        return
    here = os.path.dirname(os.path.abspath(__file__))
    roots = {here, os.path.dirname(here)}
    if not session.config.args or not all(
            os.path.abspath(a.rstrip("/")) in roots
            for a in session.config.args):
        return
    from test_op_coverage import executed_required_ops

    missing = sorted(executed_required_ops() - EXECUTED_OP_TYPES)
    if missing:
        tr = session.config.pluginmanager.get_plugin("terminalreporter")
        msg = ("op-coverage audit: %d required reference ops were never "
               "EXECUTED by this test session: %s" % (len(missing), missing))
        if tr:
            tr.write_line("FAILED " + msg, red=True)
        else:
            print(msg)
        session.exitstatus = 1
