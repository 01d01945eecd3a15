"""The check that decides ``correct``, against faults planted in the timed
path: the harness runs on the CPU at a tiny size (its look for a chip
skipped) with the program broken underneath, and ``correct`` must come out
false; unbroken, it comes out true."""
import time

import pytest

from bench import run as bench_run
from bench_helpers import (CPU_PEAKS, TINY_AGG1_CELL, TINY_CELL,  # noqa: F401
                           no_persistent_cache, tiny_root)


def _run(root, workload=TINY_CELL):
    args = ["--workload", workload, "--seed", "2147483659", "--seconds", "1",
            "--trace", "0"]
    return bench_run.run(args, root=root, require_tpu=False, peaks=CPU_PEAKS,
                         t_start=time.perf_counter())


def _unchanged_state(monkeypatch):
    """Every optimizer step returns the adapters and its state unchanged."""
    from repro.optim.adamw import AdamW
    monkeypatch.setattr(AdamW, "update",
                        lambda self, grads, state, params: (params, state))


def _half_batch(monkeypatch):
    """The loss leaves out half of the batch, the mean taken over the rest."""
    from repro.models import layers
    xent = layers.softmax_xent

    def half(logits, targets, ignore_id=-1):
        n = logits.shape[0] // 2
        return xent(logits[:n], targets[:n], ignore_id)

    monkeypatch.setattr(layers, "softmax_xent", half)


@pytest.mark.parametrize("workload", [TINY_CELL, TINY_AGG1_CELL],
                         ids=["agg_interval_5", "agg_interval_1"])
def test_sound_run_is_correct(tiny_root, workload):
    res = _run(tiny_root, workload)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1


PLANTS = pytest.mark.parametrize("plant", [_unchanged_state, _half_batch],
                                 ids=["unchanged_state", "half_batch"])


def _assert_caught(res):
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@PLANTS
def test_planted_fault_is_not_correct(tiny_root, monkeypatch, plant):
    plant(monkeypatch)
    _assert_caught(_run(tiny_root))


@PLANTS
def test_planted_fault_is_not_correct_at_agg_interval_1(tiny_root, monkeypatch,
                                                         plant):
    plant(monkeypatch)
    _assert_caught(_run(tiny_root, TINY_AGG1_CELL))
