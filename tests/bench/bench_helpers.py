"""Helpers and fixtures of the benchmark's tests: the repository root, and
tiny cells added to a copy of the benchmark as a later change would add
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
TINY_AGG1_CELL = "bert-tiny6-agg1"
# the harness's peaks for a run on the CPU (tests only; never a device number)
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _register(root: Path, workload: str, traffic: str, with_config: bool) -> None:
    """Add a cell of the tiny configuration (and, ``with_config``, the
    configuration's entry) to ``root``'s BENCHMARK.json, and the cell to
    every per-layer metric's list."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    if with_config:
        spec["configs"].append({"name": "bert-tiny", "source": "test",
                                "file": "bench/configs/bert-tiny.json",
                                "reduced": [], "why": "CPU rehearsal"})
    spec["workloads"].append({"name": workload, "config": "bert-tiny",
                              "traffic": traffic, "chips": 1,
                              "why": "CPU rehearsal"})
    for m in spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(workload)
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))


def add_tiny_cell(root: Path, reference: str = "transformer") -> None:
    """A bert-shaped 4-layer configuration, replayed by the reference
    module ``reference``, and a small paper6-like traffic mix, registered
    in ``root``'s BENCHMARK.json."""
    c = json.loads((ROOT / "bench/configs/bert-base.json").read_text())
    sizes = {"hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
             "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 128,
             "vocab_size": 4096, "max_position_embeddings": 64}
    c.update(sizes, name="bert-tiny", reference=reference)
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
    _register(root, TINY_CELL, "tiny6", with_config=True)


def add_tiny_agg1_cell(root: Path) -> None:
    """The tiny cell's traffic with a sync commit every round, as
    ``paper6-agg1`` is ``paper6``'s."""
    t = json.loads((root / "bench/traffic/tiny6.json").read_text())
    t.update(name="tiny6-agg1", agg_interval=1)
    (root / "bench/traffic/tiny6-agg1.json").write_text(json.dumps(t, indent=1))
    _register(root, TINY_AGG1_CELL, "tiny6-agg1", with_config=False)


def copy_benchmark(root: Path) -> Path:
    """BENCHMARK.json and bench/ copied into ``root``, as a checkout of the
    benchmark holds them."""
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def added_files(root) -> set:
    """The files under ``root``'s bench/ that the benchmark lacks; asserts
    that every file it has is unchanged."""
    new = set()
    for p in (root / "bench").rglob("*"):
        if not p.is_file() or "__pycache__" in p.parts:
            continue
        have = ROOT / p.relative_to(root)
        if have.exists():
            assert p.read_bytes() == have.read_bytes(), p
        else:
            new.add(p.relative_to(root).as_posix())
    return new


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    """A checkout of the benchmark (BENCHMARK.json and bench/) with the
    tiny cell, and its variant that commits every round, added."""
    root = copy_benchmark(tmp_path_factory.mktemp("bench_root"))
    add_tiny_cell(root)
    add_tiny_agg1_cell(root)
    return root


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch, tmp_path):
    """Keep the harness from turning on JAX's persistent cache in the
    test worker: with the variable set it leaves the cache to JAX, which
    read its settings at import."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "unused"))
