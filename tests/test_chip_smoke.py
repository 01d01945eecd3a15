"""chip_smoke.py off the chip: its three phases at a tiny size on the CPU
(control flow, checks and printed records; the kernels run interpreted),
its refusal to run anywhere but a TPU, and where it puts the compile
cache.  The real-size run is ``python chip_smoke.py`` on the chip."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import REGISTRY, reduced
from repro.data import make_emotion_dataset
from repro.launch.compile_cache import use_compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_run_and_agree_at_tiny_size(smoke, monkeypatch, capsys):
    # lowering to Mosaic needs the chip; here the kernels run interpreted
    monkeypatch.setattr(smoke, "assert_kernels_compiled", lambda cfg, rows: 0)
    cfg = reduced(REGISTRY["bert-base"], n_layers=4,
                  d_model=64).with_(vocab_size=4096)
    train = make_emotion_dataset(600, seq_len=16, vocab_size=4096, seed=0)
    test = make_emotion_dataset(64, seq_len=16, vocab_size=4096, seed=1)
    recs = smoke.run_phases(cfg, train, test, batch=4)
    assert [r["phase"] for r in recs] == ["seq", "batched", "kernels"]
    for r in recs:
        assert len(r["round_mean_loss"]) == smoke.ROUNDS
        assert len(r["client_loss_round0"]) == 6
        assert 0.0 <= r["accuracy"] <= 1.0
        assert r["n_compiles"] > 0
        assert 0.0 < r["compile_s"] <= r["wall_s"]
        assert r["sim_clock_simulated_s"] > 0
    lines = [json.loads(s) for s in capsys.readouterr().out.splitlines()]
    assert [ln.get("phase") for ln in lines[:3]] == ["seq", "batched",
                                                     "kernels"]
    assert [ln["compare"] for ln in lines[3:]] == ["batched vs seq",
                                                   "kernels vs batched"]
    assert not any("ok" in ln for ln in lines)


def test_compile_clock_merges_nested_spans(smoke):
    clock = smoke.CompileClock()
    clock.spans = [(0.0, 4.0), (1.0, 2.0), (3.0, 6.0), (8.0, 9.0)]
    assert clock.seconds() == 7.0


def _run_script(path: Path, cwd: Path, tmp_path: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(path)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_script_refuses_without_a_chip(where, tmp_path):
    """On the CPU, and with no repository beside it, the script exits
    non-zero with a reason and prints no result."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        lone = tmp_path / "lone"
        lone.mkdir()
        script = Path(shutil.copy(script, lone))
    out = _run_script(script, script.parent, tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "chip_smoke:" in out.stderr.strip().splitlines()[-1]


def test_compile_cache_prefers_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache(tmp_path) == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_compile_cache_defaults_to_the_checkout(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache(tmp_path)
        assert path == str(tmp_path.resolve() / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
