"""The check that decides ``correct``, against faults planted in the timed
path: the harness runs on the CPU at a tiny size (its look for a chip
skipped) with the program broken underneath, and ``correct`` must come out
false; unbroken, it comes out true."""
import time

import pytest

from bench import run as bench_run
from bench_helpers import CPU_PEAKS, TINY_CELL, no_persistent_cache, tiny_root  # noqa: F401

ARGS = ["--workload", TINY_CELL, "--seed", "2147483659", "--seconds", "1",
        "--trace", "0"]


def _run(root):
    return bench_run.run(ARGS, root=root, require_tpu=False, peaks=CPU_PEAKS,
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


def test_sound_run_is_correct(tiny_root):
    res = _run(tiny_root)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1


@pytest.mark.parametrize("plant", [_unchanged_state, _half_batch],
                         ids=["unchanged_state", "half_batch"])
def test_planted_fault_is_not_correct(tiny_root, monkeypatch, plant):
    plant(monkeypatch)
    res = _run(tiny_root)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
