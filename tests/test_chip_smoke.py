"""chip_smoke.py at tiny sizes on the CPU, and the bring-up rules it rests on:
the script refuses to report without a TPU, the compile cache lives where the
one resolver says (core/compile_cache.py), a tier-B restore loads onto the
executable's own device with 8 local devices present, a place that names no
device raises, and a serving client beside a server never opens a JAX
backend (on a chip machine it would fight the server for the chip).

The smoke's real run is on the chip (`python chip_smoke.py`); what runs here
is labelled "not a chip result" by the script itself.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import compile_cache as cc
from paddle_tpu.core import telemetry as tm
from test_compile_cache import _build, _feed

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_ROOT, "chip_smoke.py")


def _run(argv, env_extra, timeout=300):
    env = dict(os.environ)          # conftest: JAX_PLATFORMS=cpu, 8 devices
    env.pop("FLAGS_compile_cache_dir", None)
    env.update(env_extra)
    return subprocess.run([sys.executable] + argv, env=env, cwd=_ROOT,
                          capture_output=True, text=True, timeout=timeout)


def _snippet(code, env_extra):
    out = _run(["-c", "import sys; sys.path.insert(0, %r)\n%s" % (_ROOT, code)],
               env_extra)
    assert out.returncode == 0, out.stdout + out.stderr
    return out.stdout.strip().splitlines()[-1]


def test_legs_abc_tiny_and_cache_under_the_placed_dir(tmp_path):
    placed = str(tmp_path / "placed")
    out = _run([_SMOKE, "--test-tiny-on-cpu", "--legs", "A,B,C"],
               {"JAX_COMPILATION_CACHE_DIR": placed})
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-4000:]
    for leg in "ABC":
        assert "leg %s: passed" % leg in out.stdout, out.stdout[-4000:]
    assert "FAIL" not in out.stdout
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is True and last["not_a_chip_result"] is True
    assert last["device"]["platform"] == "cpu"
    # JAX_COMPILATION_CACHE_DIR set: the whole cache is under it — tier B
    # in aot/, tier A's files beside it
    assert "compile cache at %s" % placed in out.stdout
    assert os.listdir(os.path.join(placed, "aot"))
    assert [n for n in os.listdir(placed) if n != "aot"]


def test_refuses_to_report_without_a_tpu():
    out = _run([_SMOKE], {})
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "not a tpu" in out.stderr
    # interpret mode leaking in is refused before JAX is even imported
    out = _run([_SMOKE], {"PADDLE_PALLAS_INTERPRET": "1"})
    assert out.returncode != 0 and '"ok"' not in out.stdout


_TINY_RUN = """
import numpy as np, jax
import paddle_tpu as fluid
from paddle_tpu.core import compile_cache as cc
writes = []
_update = jax.config.update
def spy(name, val):
    writes.append(name)
    return _update(name, val)
jax.config.update = spy
where = cc.place(%(path)r)
main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = fluid.layers.data("x", shape=[4])
    loss = fluid.layers.mean(fluid.layers.fc(x, 3))
exe = fluid.Executor(fluid.CPUPlace())
exe.run(startup)
exe.run(main, feed={"x": np.ones((2, 4), "f")}, fetch_list=[loss])
import json
print(json.dumps({"where": where, "xla": cc.xla_dir(), "aot": cc.aot_dir(),
                  "entries": len(cc.entries()),
                  "jax": jax.config.jax_compilation_cache_dir,
                  "wrote_dir": "jax_compilation_cache_dir" in writes,
                  "flag": fluid.get_flags(["FLAGS_compile_cache_dir"])}))
"""


def test_resolver_env_set_wins_and_package_never_writes_jax_config(tmp_path):
    placed, other = str(tmp_path / "placed"), str(tmp_path / "other")
    got = json.loads(_snippet(_TINY_RUN % {"path": other},
                              {"JAX_COMPILATION_CACHE_DIR": placed}))
    assert got["where"] == got["xla"] == got["jax"] == placed
    assert got["aot"] == os.path.join(placed, "aot")
    assert got["entries"] >= 2          # startup + main landed in tier B
    assert got["wrote_dir"] is False    # JAX read the variable itself
    assert got["flag"] == {"FLAGS_compile_cache_dir": ""}
    assert not os.path.exists(other)


def test_resolver_env_unset_is_the_fixed_in_checkout_path(tmp_path):
    code = ("from paddle_tpu.core import compile_cache as cc\n"
            "print(cc.DEFAULT_DIR)")
    assert _snippet(code, {}) == os.path.join(_ROOT, ".jax_cache")
    assert cc.DEFAULT_DIR == os.path.join(_ROOT, ".jax_cache")
    # an explicit FLAGS_compile_cache_dir still chooses when the variable
    # is unset, and then tier A is wired under it
    chosen = str(tmp_path / "chosen")
    got = json.loads(_snippet(_TINY_RUN % {"path": None},
                              {"FLAGS_compile_cache_dir": chosen}))
    assert got["where"] == chosen
    assert got["xla"] == got["jax"] == os.path.join(chosen, "xla")
    assert got["entries"] >= 2


@pytest.mark.parametrize("place", [fluid.CPUPlace(), fluid.TPUPlace(3)])
def test_tier_b_restore_with_8_local_devices(tmp_path, place):
    """The seed's 25 failures: deserialize_and_load without
    execution_devices spread a one-device executable over all 8."""
    import jax

    assert len(jax.devices()) == 8
    old = fluid.get_flags(["FLAGS_compile_cache_dir", "FLAGS_telemetry"])
    fluid.set_flags({"FLAGS_compile_cache_dir": str(tmp_path / "cc"),
                     "FLAGS_telemetry": True})
    try:
        def once():
            main, startup, loss = _build()   # same content, new Program
            exe = fluid.Executor(place)
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe.run(startup)
                src = exe.warmup(main, feed_specs=_feed(),
                                 fetch_list=[loss])["source"]
                outs = [float(np.asarray(exe.run(
                    main, feed=_feed(), fetch_list=[loss])[0]).reshape(-1)[0])
                    for _ in range(2)]
                w = scope.find_var("cct_w1").get_tensor().get()
            return src, outs, w

        src0, outs0, _ = once()
        xla0 = tm.counter_total("executor_xla_compile_total")
        src1, outs1, w = once()
        assert (src0, src1) == ("compiled", "disk")
        assert tm.counter_total("executor_xla_compile_total") == xla0
        assert outs1 == outs0
        assert w.devices() == {place.jax_device()}
    finally:
        fluid.set_flags(old)
    # chip 0's executable is not chip 3's: the device set is in the key
    assert len({r["key"] for r in cc.entries()}) == len(cc.entries())


def test_place_that_names_no_device_raises():
    import jax

    n = len(jax.devices())
    assert fluid.TPUPlace(n - 1).jax_device() == jax.devices()[n - 1]
    with pytest.raises(RuntimeError, match="such device"):
        fluid.TPUPlace(n).jax_device()       # was: chip n % count
    with pytest.raises(RuntimeError, match="such device"):
        fluid.Executor(fluid.TPUPlace(n)).run(fluid.Program())


# case -> (mesh shape over ("data", "model") or None for with_data_parallel's
# own ("data",) mesh, devices, batch, flags, the reason fused_ln counts)
WRAP_RULE = {
    # the data axis is the mesh's only one and divides the batch: the op
    # runs per shard, where the family's own checks decide (off the chip
    # the first of them, ``backend``, declines)
    "data_mesh": (None, 4, 8, {}, "backend"),
    "data_mesh_of_8": (None, 8, 8, {}, "backend"),
    # everything else keeps the composition XLA partitions, by name
    "dp_x_tp_mesh": ((4, 2), 8, 8, {}, "gspmd_mesh"),
    "ragged_batch": (None, 4, 6, {}, "gspmd_mesh"),
    "deterministic_reduction": (
        None, 4, 8, {"FLAGS_deterministic_reduction": True}, "gspmd_mesh"),
}


@pytest.mark.parametrize("case", sorted(WRAP_RULE))
def test_fused_ln_runs_per_shard_where_the_mesh_only_splits_the_batch(case):
    """On four real chips with_data_parallel died at lowering: "Mosaic
    kernels cannot be automatically partitioned ... wrap the call in a
    shard_map".  PR 24 made the funnel decline there (``gspmd_mesh``);
    since PR 39 the epilogue's two lowerings wrap their own call where the
    lowering sees a data-only mesh that divides the batch, and inside the
    wrap the funnel decides as on one chip."""
    import jax
    from jax.sharding import Mesh

    shape, n, batch, flags, expected = WRAP_RULE[case]
    old = fluid.get_flags(["FLAGS_telemetry", *flags])
    fluid.set_flags({"FLAGS_telemetry": True, **flags})
    tm.reset()
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[4, 128])
            y = fluid.layers.fc(x, 128, num_flatten_dims=2)
            loss = fluid.layers.mean(
                fluid.layers.fused_dropout_add_ln(x, y, dropout_prob=0.1,
                                                  begin_norm_axis=2))
            fluid.optimizer.SGD(0.1).minimize(loss)
        compiled = fluid.CompiledProgram(main)
        if shape is None:
            compiled.with_data_parallel(
                loss_name=loss.name,
                places=[fluid.TPUPlace(i) for i in range(n)])
        else:
            compiled._with_mesh(
                Mesh(np.array(jax.devices()[:n]).reshape(shape),
                     ("data", "model")), data_axis="data")
        exe = fluid.Executor(fluid.TPUPlace(0))
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            out, = exe.run(compiled, feed={"x": np.ones((batch, 4, 128), "f")},
                           fetch_list=[loss])
        assert np.isfinite(out).all()
        reasons = [ls["reason"] for _flat, ls in
                   tm.label_sets("pallas_kernel_fallback_total")
                   if ls["kernel"] == "fused_ln"]
        # the op and its grad op, one rule: both count the same reason
        assert reasons == [expected], reasons
        assert tm.counter_total("pallas_kernel_fallback_total") == 2
        assert tm.counter_total("pallas_kernel_used_total") == 0
    finally:
        tm.reset()
        fluid.set_flags(old)


def test_per_shard_lifts_the_guard_only_inside_the_wrap():
    """Under the bare guard the funnel declines before it looks at a check;
    inside ``per_shard`` the checks decide; leaving it restores the guard."""
    from paddle_tpu.pallas_kernels import adoption

    with adoption.auto_partitioned():
        assert adoption.decide(
            "fused_ln", [("backend", True)]) == (False, "gspmd_mesh")
        with adoption.per_shard():
            assert adoption.decide(
                "fused_ln", [("backend", False)]) == (False, "backend")
            assert adoption.decide("fused_ln", [("backend", True)])[0]
        assert adoption.decide(
            "fused_ln", [("backend", True)]) == (False, "gspmd_mesh")
    adoption.reset()


def test_client_beside_a_server_never_opens_a_jax_backend():
    from paddle_tpu.serving import DecodeEngine, ServingEngine, ServingServer
    from paddle_tpu.serving.decode_model import (DecoderConfig,
                                                 init_decoder_params)

    cfg = DecoderConfig(vocab=31, layers=1, heads=2, head_dim=8, max_seq=32)
    engine = DecodeEngine(buckets="1", deadline_ms=30000.0)
    engine.add_model("toy", (cfg, init_decoder_params(cfg, seed=1)),
                     kv_blocks=8)
    engine.prewarm()
    engine.start()
    server = ServingServer(ServingEngine(), port=0,
                           decode_engine=engine).start()
    code = """
sys.path.insert(0, %r)
import loadgen  # the load generator's imports count too
from paddle_tpu.serving import ServingClient
r = ServingClient(endpoints=["127.0.0.1:%d"]).generate(
    "toy", [1, 2, 3], max_new_tokens=4, deadline_ms=30000.0)
from jax._src import xla_bridge
print(r.status, len(r.outputs["tokens"]), xla_bridge.backends_are_initialized())
""" % (os.path.join(_ROOT, "tools"), server.port)
    try:
        assert _snippet(code, {}) == "ok 4 False"
    finally:
        server.shutdown()
        engine.stop()
