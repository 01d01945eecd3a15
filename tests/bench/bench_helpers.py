"""Helpers and fixtures of the benchmark's tests: the repository root, and
a tiny cell added to a copy of the benchmark as a later change would add
one (new files and a BENCHMARK.json entry, no code).  No ``conftest.py``:
the repository's tests import their own ``conftest`` by that name."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:       # ``bench`` is imported from the root
    sys.path.insert(0, str(ROOT))

TINY_CELL = "bert-tiny6"
# the harness's peaks for a run on the CPU (tests only; never a device number)
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def add_tiny_cell(root: Path) -> None:
    """A bert-shaped 4-layer configuration and a small paper6-like traffic
    mix, registered in ``root``'s BENCHMARK.json."""
    c = json.loads((ROOT / "bench/configs/bert-base.json").read_text())
    sizes = {"hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
             "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 128,
             "vocab_size": 4096, "max_position_embeddings": 64}
    c.update(sizes, name="bert-tiny")
    c["lora"] = {"rank": 4, "alpha": 8.0, "targets": ["wq", "wk", "wv", "wo"]}
    c["program"] = {"registry": "bert-base",
                    "with": {"d_model": 64, "n_layers": 4, "n_heads": 4,
                             "n_kv_heads": 4, "head_dim": 16, "d_ff": 128,
                             "vocab_size": 4096, "max_position": 64},
                    "lora": {"rank": 4, "alpha": 8.0}}
    (root / "bench/configs/bert-tiny.json").write_text(json.dumps(c, indent=1))
    t = json.loads((ROOT / "bench/traffic/paper6.json").read_text())
    t.update(name="tiny6", batch=4, seq_len=32, n_train=600)
    (root / "bench/traffic/tiny6.json").write_text(json.dumps(t, indent=1))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "bert-tiny", "source": "test",
                            "file": "bench/configs/bert-tiny.json",
                            "reduced": [], "why": "CPU rehearsal"})
    spec["workloads"].append({"name": TINY_CELL, "config": "bert-tiny",
                              "traffic": "tiny6", "chips": 1,
                              "why": "CPU rehearsal"})
    for m in spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(TINY_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    """A checkout of the benchmark (BENCHMARK.json and bench/) with the
    tiny cell added."""
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    add_tiny_cell(root)
    return root


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch, tmp_path):
    """Keep the harness from turning on JAX's persistent cache in the
    test worker: with the variable set it leaves the cache to JAX, which
    read its settings at import."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "unused"))
