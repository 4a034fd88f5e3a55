"""The whole command at tiny size on the CPU: the last line parses and
carries every metric BENCHMARK.json names for the cell; and a new
configuration, traffic mix and per-layer metric dropped into a copy of the
benchmark are found without editing a file that was there."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
# read from the device trace: a CPU run has no device plane to read, and no
# published peak to divide an mfu by
NEEDS_CHIP = {"device_trace"}


def run_cell(root, cell, trace, cache, seconds=2):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(2 ** 31 + 12345),
         "--seconds", str(seconds), "--trace", str(trace),
         "--rehearse-tiny-on-cpu"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def expected(bench, cell, group, on_chip):
    return {m["name"] for m in bench[group]
            if cell in m.get("workloads", [cell])
            and (on_chip or group == "end_to_end"
                 or (m["source"] not in NEEDS_CHIP
                     and "mfu" not in m["name"]))}


@pytest.mark.parametrize("cell", ["bert_base_train_seq128",
                                  "gpt2_medium_serve_decode_heavy"])
@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_carries_the_cells_metrics(cell, trace, tmp_path):
    if cell not in {w["name"] for w in BENCH["workloads"]}:
        pytest.skip("cell not in BENCHMARK.json")
    line = run_cell(ROOT, cell, trace, tmp_path / "cache")
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["not_a_chip_result"] is True
    group = "per_layer" if trace else "end_to_end"
    assert set(line["metrics"]) >= expected(BENCH, cell, group, False)
    assert all(isinstance(m["value"], float) and m["unit"]
               for m in line["metrics"].values())
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_refuses_without_a_tpu(tmp_path):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seconds", "1"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and not out.stdout.strip().startswith("{")


def test_new_files_are_found_without_editing_old_ones(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "paddle_tpu"), root / "paddle_tpu")
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    bench = json.loads(json.dumps(BENCH))
    # a configuration, a mix and a per-layer metric, as files of their own
    config = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "gpt2-medium-serve.json")))
    config.update(name="tiny-decoder", n_layer=3)
    config["tiny"]["n_layer"] = 3
    (root / "benchmark" / "configs" / "tiny-decoder.json").write_text(
        json.dumps(config))
    mix = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", "serve_decode_heavy.json")))
    mix["tiny"].update(clients=3, lane_buckets=[3], size_set=5)
    (root / "benchmark" / "traffic" / "serve_three_callers.json").write_text(
        json.dumps(mix))
    (root / "benchmark" / "layer_metrics" / "steps_seen.serve.py").write_text(
        "def read(obs):\n    return float(len(obs['decode_spans']))\n")
    bench["configs"].append(dict(
        bench["configs"][1], name="tiny-decoder",
        file="benchmark/configs/tiny-decoder.json"))
    bench["workloads"].append({
        "name": "added_cell", "config": "tiny-decoder",
        "traffic": "serve_three_callers", "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "gpt2_medium_serve_decode_heavy" in metric.get("workloads", []):
            metric["workloads"].append("added_cell")
    bench["per_layer"].append({
        "name": "steps_seen.serve", "unit": "count", "better": "higher",
        "source": "program_span", "layer": "scheduler",
        "moves": "serve_tokens_per_s", "workloads": ["added_cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    line = run_cell(str(root), "added_cell", 1, tmp_path / "cache")
    assert line["correct"] is True
    assert line["metrics"]["steps_seen.serve"]["value"] > 0
    assert set(line["metrics"]) >= expected(bench, "added_cell", "per_layer",
                                            False)
    line = run_cell(str(root), "added_cell", 0, tmp_path / "cache")
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    after = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
             if p.is_file() and "__pycache__" not in str(p)}
    assert all(after[p] == data for p, data in before.items())
