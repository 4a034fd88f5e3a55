"""Two-tier persistent compilation cache (core/compile_cache.py + the
executor's eager-AOT compile path).

The headline guarantee rides a real second process: pointed at a cache
directory a previous process populated, it must run the identical
program with ZERO XLA compiles (every executable restored from tier B)
and a bitwise-identical fetch stream.  The in-process tests cover the
failure modes around that guarantee: corrupted artifacts and manifest
version skew recompile cleanly (and scrub the bad entry so the rewrite
sticks), the LRU cap actually evicts, warmup() pre-populates both the
in-memory and on-disk caches, and the tier-B key is content-based —
stable across rebuilds, sensitive to trace-affecting flags.
"""

import contextlib
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import compile_cache as cc
from paddle_tpu.core import executor as executor_mod
from paddle_tpu.core import telemetry as tm

_PAYLOAD = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "compile_cache_payload.py")


@contextlib.contextmanager
def _flags(**kv):
    kv = {("FLAGS_" + k if not k.startswith("FLAGS_") else k): v
          for k, v in kv.items()}
    old = fluid.get_flags(list(kv))
    fluid.set_flags(kv)
    try:
        yield
    finally:
        fluid.set_flags(old)


def _counters():
    return dict(tm.snapshot()["counters"])


def _delta(before, name):
    return _counters().get(name, 0) - before.get(name, 0)


def _build():
    """One deterministic toy regression; identical content every call
    (unique_name.guard resets the temp-name counters) so every rebuild
    maps to the SAME tier-B key while missing the in-memory cache."""
    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 7
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[4])
            y = fluid.layers.data("y", shape=[1])
            h = fluid.layers.fc(x, 8, act="relu",
                                param_attr=fluid.ParamAttr(name="cct_w1"))
            pred = fluid.layers.fc(h, 1,
                                   param_attr=fluid.ParamAttr(name="cct_w2"))
            loss = fluid.layers.mean(fluid.layers.square(pred - y))
            fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _feed():
    rng = np.random.RandomState(5)
    return {"x": rng.rand(8, 4).astype("f"), "y": rng.rand(8, 1).astype("f")}


def _run_once(fetch_twice=False):
    """Fresh scope + fresh program build: in-memory caches can't help, so
    every executable either restores from tier B or recompiles."""
    main, startup, loss = _build()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        out = exe.run(main, feed=_feed(), fetch_list=[loss.name])
        if fetch_twice:
            exe.run(main, feed=_feed(), fetch_list=[loss.name])
    return float(np.asarray(out[0]).reshape(-1)[0])


def _main_entry():
    """The tier-B entry of the training step (the only 2-feed program)."""
    ents = [r for r in cc.entries() if r["meta"].get("n_feeds") == 2]
    assert ents, cc.entries()
    return ents[-1]


# ---------------------------------------------------------------------------
# cross-process reuse (the headline guarantee)


def _spawn_payload(cache_dir):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, _PAYLOAD, cache_dir], env=env,
        capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stdout + out.stderr
    c = re.search(r"counters: xla=(\d+) disk_hits=(\d+) stores=(\d+) "
                  r"aot_fallback=(\d+)", out.stdout)
    f = re.search(r"fetch: ([0-9a-f]+)", out.stdout)
    assert c and f, out.stdout + out.stderr
    return {"xla": int(c.group(1)), "disk_hits": int(c.group(2)),
            "stores": int(c.group(3)), "aot_fallback": int(c.group(4)),
            "fetch": f.group(1)}


def test_cross_process_reuse(tmp_path):
    d = str(tmp_path / "cc")
    first = _spawn_payload(d)
    # cold process: compiled (startup + main) and persisted both
    assert first["xla"] >= 2 and first["stores"] >= 2, first
    assert first["aot_fallback"] == 0, first

    second = _spawn_payload(d)
    # warm process: ZERO XLA compiles — everything restored from tier B —
    # and the training trajectory is bitwise identical
    assert second["xla"] == 0, second
    assert second["disk_hits"] >= 2, second
    assert second["fetch"] == first["fetch"], (first, second)


# ---------------------------------------------------------------------------
# corruption / skew: recompile cleanly, scrub the bad entry


def test_truncated_artifact_recompiles(tmp_path):
    with _flags(compile_cache_dir=str(tmp_path / "cc"), telemetry=True):
        loss0 = _run_once()
        ent = _main_entry()
        art = os.path.join(cc.aot_dir(), ent["key"], "executable.bin")
        blob = open(art, "rb").read()
        with open(art, "wb") as f:
            f.write(blob[:len(blob) // 2])

        before = _counters()
        loss1 = _run_once()
        assert _delta(before, "compile_cache_errors_total{kind=crc}") >= 1
        assert _delta(before, "executor_xla_compile_total") >= 1
        assert loss1 == loss0
        # the defective entry was scrubbed and re-stored whole
        fresh = [r for r in cc.entries() if r["key"] == ent["key"]]
        assert fresh and fresh[0]["valid"], cc.entries()
        # whole again (a recompile serializes to a slightly different
        # size, so compare against the truncation, not the original)
        assert os.path.getsize(art) > len(blob) // 2

        before = _counters()
        _run_once()
        assert _delta(before, "executor_xla_compile_total") == 0
        assert _delta(before, "compile_cache_disk_hit_total") >= 2


def test_version_mismatch_recompiles(tmp_path):
    with _flags(compile_cache_dir=str(tmp_path / "cc"), telemetry=True):
        _run_once()
        ent = _main_entry()
        man_path = os.path.join(cc.aot_dir(), ent["key"], "_SUCCESS")
        man = json.load(open(man_path))
        man["jax"] = "0.0.0-stale"
        with open(man_path, "w") as f:
            json.dump(man, f)

        before = _counters()
        _run_once()
        assert _delta(before,
                      "compile_cache_errors_total{kind=version}") >= 1
        assert _delta(before, "executor_xla_compile_total") >= 1
        # rewritten under the live jax version -> next process hits again
        before = _counters()
        _run_once()
        assert _delta(before, "executor_xla_compile_total") == 0


def test_lru_eviction(tmp_path):
    with _flags(compile_cache_dir=str(tmp_path / "cc"), telemetry=True):
        _run_once()
        n = len(cc.entries())
        assert n >= 2  # startup + main
        # cap below the current footprint: the next store must evict
        total = sum(r["bytes"] for r in cc.entries())
        with _flags(compile_cache_max_bytes=total // 2):
            before = _counters()
            evicted = cc.evict_to_cap()
            assert evicted >= 1
            assert _delta(before, "compile_cache_evictions_total") >= 1
            assert sum(r["bytes"] for r in cc.entries()) <= total // 2


def test_clear_wipes_both_tiers(tmp_path):
    with _flags(compile_cache_dir=str(tmp_path / "cc"), telemetry=True):
        _run_once()
        assert cc.stats()["aot_entries"] >= 2
        cc.clear()
        st = cc.stats()
        assert st["aot_entries"] == 0 and st["xla_files"] == 0


# ---------------------------------------------------------------------------
# warmup(): compile without running a step


def test_warmup_then_run_no_extra_compile(tmp_path):
    with _flags(compile_cache_dir=str(tmp_path / "cc"), telemetry=True):
        main, startup, loss = _build()
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            before = _counters()
            got = exe.warmup(main,
                             feed_specs={"x": ((8, 4), "float32"),
                                         "y": ((8, 1), "float32")},
                             fetch_list=[loss.name])
            assert got["source"] in ("compiled", "disk"), got
            assert _delta(before, "executor_warmup_total") == 1
            mid = _counters()
            out, = exe.run(main, feed=_feed(), fetch_list=[loss.name])
            assert np.isfinite(float(np.asarray(out).reshape(-1)[0]))
            # the step ran on the warmed executable: no compile, no miss
            assert _delta(mid, "executor_xla_compile_total") == 0
            assert _delta(mid, "executor_cache_miss_total") == 0
            # second warmup is an in-memory no-op
            got2 = exe.warmup(main,
                              feed_specs={"x": ((8, 4), "float32"),
                                          "y": ((8, 1), "float32")},
                              fetch_list=[loss.name])
            assert got2["source"] == "memory", got2


# ---------------------------------------------------------------------------
# key semantics


def test_artifact_key_stable_and_flag_sensitive(tmp_path):
    feed_sig = (("x", (8, 4), "float32"),)
    tf = (("FLAGS_check_nan_inf", False),)
    main1, _s1, loss1 = _build()
    main2, _s2, loss2 = _build()
    k1 = cc.artifact_key(main1, feed_sig, (loss1.name,), tf)
    k2 = cc.artifact_key(main2, feed_sig, (loss2.name,), tf)
    # content-based: a rebuild of the identical program shares the key
    assert k1 == k2
    # trace-affecting flags partition the key space
    k3 = cc.artifact_key(main1, feed_sig, (loss1.name,),
                         (("FLAGS_check_nan_inf", True),))
    assert k3 != k1
    # so does the collective world
    main1._collective_meta = {"nranks": 2, "mode": "allreduce"}
    try:
        k4 = cc.artifact_key(main1, feed_sig, (loss1.name,), tf)
    finally:
        del main1._collective_meta
    assert k4 != k1


@pytest.mark.parametrize("key", ["artifact_key", "raw_artifact_key"])
def test_another_checkouts_lowerings_never_share_a_key(monkeypatch, key):
    """PR 39's chip runs: parent and change shared one cache directory and
    one Program, and the change restored the parent's executable (the
    composed epilogue, 436k tokens/s where its own compiled to 472k).  The
    key now carries the package's sources: stable within a checkout,
    different where any lowering differs."""
    main, _s, loss = _build()

    def make():
        if key == "artifact_key":
            return cc.artifact_key(main, (("x", (8, 4), "float32"),),
                                   (loss.name,), ())
        return cc.raw_artifact_key("decode_step", {"bucket": 32})

    here = cc.code_fingerprint()
    assert here == cc.code_fingerprint() and len(here) == 64
    k1 = make()
    assert k1 == make()
    monkeypatch.setattr(cc, "_code_fp", ["another checkout's sources"])
    assert make() != k1


_FLIPPED = {"FLAGS_check_nan_inf": True, "FLAGS_bn_stat_subsample": 2,
            "FLAGS_layout_match_params": False,
            "FLAGS_deterministic_reduction": True}


@pytest.mark.parametrize("flag", executor_mod._TRACE_FLAGS)
def test_trace_flag_keys_the_cache(tmp_path, flag):
    """Each trace-affecting flag is in both keys: flipping it between two
    runs of one program is exactly one more in-memory miss and another
    tier-B artifact, and run and warmup agree on both keys under either
    setting (a warmed signature is no miss; an artifact a run stored is
    what another executor's warmup restores)."""
    specs = {"x": ((8, 4), "float32"), "y": ((8, 1), "float32")}
    with _flags(compile_cache_dir=str(tmp_path / "cc"), telemetry=True):
        main, startup, loss = _build()
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            warmed = exe.warmup(main, feed_specs=specs,
                                fetch_list=[loss.name])
            assert warmed["source"] == "compiled", warmed
            before = _counters()
            exe.run(main, feed=_feed(), fetch_list=[loss.name])
            assert _delta(before, "executor_cache_miss_total") == 0
            with _flags(**{flag: _FLIPPED[flag]}):
                exe.run(main, feed=_feed(), fetch_list=[loss.name])
                assert _delta(before, "executor_cache_miss_total") == 1
                exe.run(main, feed=_feed(), fetch_list=[loss.name])
                assert _delta(before, "executor_cache_miss_total") == 1
                again = fluid.Executor(fluid.CPUPlace()).warmup(
                    main, feed_specs=specs, fetch_list=[loss.name])
            assert again["source"] == "disk", again
            assert again["key"] and again["key"] != warmed["key"]
            # back under the first setting the first entry still serves
            exe.run(main, feed=_feed(), fetch_list=[loss.name])
            assert _delta(before, "executor_cache_miss_total") == 1
