"""The plain reference is the module that the configuration file names:
a cell whose configuration brings its own module in ``bench/reference``
is added as new files alone, and its run replays that module; a name that
is no module file there is refused, with the file's name."""
import json
import time

import pytest

from bench import run as bench_run
from bench.reference.replay import reference
from bench_helpers import (CPU_PEAKS, ROOT, TINY_CELL, add_tiny_cell,  # noqa: F401
                           added_files, copy_benchmark, no_persistent_cache)

OWN = "pooled_last"


def _args():
    return ["--workload", TINY_CELL, "--seed", "2147483677", "--seconds", "1",
            "--trace", "0"]


def _set_reference(root, name):
    path = root / "bench/configs/bert-tiny.json"
    c = json.loads(path.read_text())
    c["reference"] = name
    path.write_text(json.dumps(c, indent=1))


@pytest.fixture(scope="module")
def own_root(tmp_path_factory):
    """A copy of the benchmark with the tiny cell, whose configuration
    names ``pooled_last``: the transformer reference with a planted change,
    its classifier pooling the last position instead of the first."""
    root = copy_benchmark(tmp_path_factory.mktemp("own_reference"))
    add_tiny_cell(root, reference=OWN)
    src = (ROOT / "bench/reference/transformer.py").read_text()
    pooled = "h = _norm(c, w[\"final_norm\"], x)[:, 0, :]"
    assert src.count(pooled) == 1
    (root / f"bench/reference/{OWN}.py").write_text(
        src.replace(pooled, pooled.replace("[:, 0, :]", "[:, -1, :]")))
    return root


def test_own_reference_is_new_files_only(own_root):
    assert added_files(own_root) == {"bench/configs/bert-tiny.json",
                                     "bench/traffic/tiny6.json",
                                     f"bench/reference/{OWN}.py"}


@pytest.mark.parametrize("name,correct", [(OWN, False), ("transformer", True)])
def test_the_named_reference_is_replayed(own_root, name, correct):
    """The program pools the first position: replayed by the planted
    module, the run is not correct; by ``transformer``, it is."""
    _set_reference(own_root, name)
    res = bench_run.run(_args(), root=own_root, require_tpu=False,
                        peaks=CPU_PEAKS, t_start=time.perf_counter())
    assert res["correct"] is correct, res["checks"]
    if not correct:
        assert res["checks"]["loss_rel"]["value"] > res["checks"]["loss_rel"]["limit"]


@pytest.mark.parametrize("name", ["nosuch", "replay", "../run", "__init__", ""])
def test_unknown_reference_is_refused_with_its_file(name):
    with pytest.raises(ValueError, match=f"bench/reference/{name}.py"):
        reference({"name": "x", "reference": name}, ROOT)


def test_run_refuses_an_unknown_reference_before_it_builds(tmp_path,
                                                          monkeypatch):
    from bench import driver

    def build(*_, **__):
        raise AssertionError("the simulator was built")

    monkeypatch.setattr(driver, "build", build)
    root = copy_benchmark(tmp_path)
    add_tiny_cell(root, reference="nosuch")
    with pytest.raises(ValueError, match="bench/reference/nosuch.py"):
        bench_run.run(_args(), root=root, require_tpu=False, peaks=CPU_PEAKS,
                      t_start=time.perf_counter())
