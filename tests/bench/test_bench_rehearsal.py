"""CPU rehearsal of ``bench/run.py``: cell loading, warm-up, window and the
result line at a tiny size; the refusals off a TPU; and a cell added as new
files plus a BENCHMARK.json entry, with no code."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import run as bench_run
from bench_helpers import (CPU_PEAKS, ROOT, TINY_CELL, added_files,  # noqa: F401
                           no_persistent_cache, tiny_root)


def _args(trace):
    return ["--workload", TINY_CELL, "--seed", "3000000001", "--seconds", "1",
            "--trace", str(trace)]


@pytest.fixture(scope="module")
def untraced(tiny_root):
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tiny_root / "unused")
    try:
        return bench_run.run(_args(0), root=tiny_root, require_tpu=False,
                             peaks=CPU_PEAKS, t_start=time.perf_counter())
    finally:
        del os.environ["JAX_COMPILATION_CACHE_DIR"]


def test_result_line_has_the_contract_keys(untraced, tiny_root, capsys):
    res = untraced
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"tokens_per_s", "mfu", "setup_s"}
    for m in res["metrics"].values():
        assert m["value"] >= 0 and m["unit"]
    assert set(res["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert res["device"]["platform"] == "cpu"
    for chk in res["checks"].values():
        assert set(chk) == {"value", "limit"}


def test_window_is_whole_rounds_after_whole_periods(untraced, tiny_root):
    spec = json.loads((tiny_root / "bench/traffic/tiny6.json").read_text())
    res = untraced
    tokens = res["attempted"] * len(spec["cuts"]) * spec["batch"] * spec["seq_len"]
    window_s = tokens / res["metrics"]["tokens_per_s"]["value"]
    assert window_s >= 1.0
    assert res["attempted"] % spec["agg_interval"] == 0
    assert res["correct"] is True


def test_traced_run_reports_per_layer_metrics_only(tiny_root, monkeypatch):
    res = bench_run.run(_args(1), root=tiny_root, require_tpu=False,
                        peaks=CPU_PEAKS, t_start=time.perf_counter())
    names = {m["name"] for m in json.loads(
        (tiny_root / "BENCHMARK.json").read_text())["per_layer"]}
    assert set(res["metrics"]) <= names
    # host-side readers find their numbers on any platform
    assert {"driver.compiles_in_window", "driver.round_max_ms"} <= set(res["metrics"])
    assert res["metrics"]["driver.compiles_in_window"]["value"] == 0
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert res["correct"] is True


def test_refuses_without_a_tpu(capsys):
    res = bench_run.run(["--workload", "bert-paper6", "--seed", "1",
                         "--seconds", "1", "--trace", "0"], require_tpu=True)
    assert res is None
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs 1 TPU chip" in out.err


def test_command_fails_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, "bench/run.py", "--workload",
                           "bert-paper6", "--seed", "1", "--seconds", "1",
                           "--trace", "0"],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in spec["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, *spec["command"][1:], "--workload", "bert-paper6",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_paper6_agg1_is_paper6_committing_every_round():
    from bench.traffic import load_traffic
    every = load_traffic(ROOT / "bench/traffic/paper6-agg1.json")
    fifth = load_traffic(ROOT / "bench/traffic/paper6.json")
    assert (every["agg_interval"], fifth["agg_interval"]) == (1, 5)
    differ = {k for k in every.keys() | fifth.keys()
              if every.get(k) != fifth.get(k)}
    assert differ == {"name", "agg_interval"}


@pytest.mark.parametrize("workload", [
    w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]])
def test_every_cell_loads_with_its_reference(workload):
    from bench.reference.replay import reference
    cell = bench_run.load_cell(ROOT, workload)
    assert cell["cell"]["name"] == workload
    assert reference(cell["config"], ROOT).dims(cell["config"])["L"] >= 1
    assert cell["end_to_end"] and cell["per_layer"]


def test_added_cell_is_data_only(tiny_root):
    """The tiny cells came with a configuration, two traffic files and
    BENCHMARK.json entries: nothing else of the copy differs from the
    benchmark."""
    assert added_files(tiny_root) == {"bench/configs/bert-tiny.json",
                                      "bench/traffic/tiny6.json",
                                      "bench/traffic/tiny6-agg1.json"}
