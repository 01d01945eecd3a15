#!/usr/bin/env python3
"""Chip smoke test: the split-federated training path on one TPU chip.

Trains the paper's bert-base at its published widths (12 layers, d_model
768, 12 heads, rank-16 adapters on q/k/v/o, float32) for two rounds through
``Simulator`` over the paper's six-client fleet (cuts 1,1,2,2,3,3), 16
sequences of 128 tokens per client, on emotion data generated from a seed.
Three phases, each a fresh ``Simulator``:

  seq      default ``EngineConfig``: the paper's sequential server, one
           jitted server step per client;
  batched  ``cohort_chunk=6``: one vmapped dispatch serves the whole fleet;
  kernels  ``cohort_chunk=6, cohort_impl="ragged", fused_lora=True``: the
           cut-grouped step through the grouped and fused Pallas kernels,
           which must lower to Mosaic (``tpu_custom_call``).

Each phase prints one JSON line: wall seconds with the seconds JAX spent
tracing, lowering and compiling apart, the losses, the accuracy, the
device's peak bytes and the cost model's simulated clock.  Then the
first-round per-client losses are compared across phases.  The last line,
printed only when everything passed, is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.

    python chip_smoke.py      # needs a TPU; exits non-zero on any other
"""
from __future__ import annotations

import gc
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SEED = 0
N_TRAIN = 2400       # ~400 examples per client under the Dirichlet split
N_TEST = 512         # evaluate() reads at most 32 batches of 16
BATCH = 16
ROUNDS = 2
# First-round per-client losses of two phases must agree to LOSS_RTOL.  XLA
# on the TPU runs float32 matmuls at default precision, which rounds their
# operands to bfloat16 (8-bit mantissa, 2^-9 relative); the Pallas kernels
# multiply the same float32 operands inside Mosaic and accumulate in
# float32 with their own rounding and order.  Through up to 11 layers and a
# 6-way softmax that can move a loss near ln 6 by about a percent.
LOSS_RTOL = 2e-2

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   _BACKEND_COMPILE)


def _require(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling while entered.

    Nested jits report nested spans, so the spans are merged before they
    are summed.  ``n_compiles`` counts backend compiles, persistent-cache
    hits included."""

    def __init__(self):
        self.spans: list = []
        self.n_compiles = 0

    def _on_span(self, event, start, end, **_):
        if event in _COMPILE_EVENTS:
            self.spans.append((start, end))
            if event == _BACKEND_COMPILE:
                self.n_compiles += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_time_span_listener(self._on_span)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_time_span_listener(self._on_span)

    def seconds(self) -> float:
        total, hi = 0.0, -math.inf
        for start, end in sorted(self.spans):
            if end > hi:
                total += end - max(start, hi)
                hi = end
        return total


def assert_kernels_compiled(cfg, rows: int) -> int:
    """Lower one fused and one grouped LoRA matmul, forward and backward,
    at ``cfg``'s widths the way the model path calls them (``interpret``
    left to the platform) and require Mosaic kernels in the program.
    Returns the number of ``tpu_custom_call`` ops found."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    k = n = cfg.d_model
    r, g = cfg.lora.rank, 6
    dt = jnp.dtype(cfg.dtype)
    calls = {
        "fused": (lambda x, w, a, b: ops.fused_lora_matmul(
                      x, w, a, b, scale=2.0), (r, k), (n, r)),
        "grouped": (lambda x, w, a, b: ops.grouped_lora_matmul(
                        x, w, a, b, group_sizes=(rows // g,) * g, scale=2.0),
                    (g, r, k), (g, n, r)),
    }
    found = 0
    for name, (fn, a_shape, b_shape) in calls.items():
        grad = jax.value_and_grad(
            lambda *args, fn=fn: fn(*args).astype(jnp.float32).sum(),
            argnums=(0, 2, 3))
        shapes = [jax.ShapeDtypeStruct(s, dt)
                  for s in ((rows, k), (k, n), a_shape, b_shape)]
        count = jax.jit(grad).lower(*shapes).as_text().count("tpu_custom_call")
        _require(count >= 2, f"kernels: the {name} LoRA matmul lowered with "
                             f"{count} tpu_custom_call ops (forward and "
                             f"backward need 2): Mosaic does not compile it")
        found += count
    return found


def build_simulator(cfg, train, test, run):
    """The paper's fleet on ``Simulator``, with every client's loss kept
    under ``client_losses[(round, uid)]``."""
    from repro.fed import PAPER_CLIENTS, PAPER_CUTS, TPU_V5E, Simulator

    class LossKeepingSimulator(Simulator):
        def _serve_group(self, grp):
            losses = super()._serve_group(grp)
            rnd = len(self.history)   # a round's record lands after serving
            self.client_losses.update(
                {(rnd, u): float(v) for u, v in zip(grp, losses)})
            return losses

    # the cost model prices the server as the assumed TPU v5e profile
    sim = LossKeepingSimulator(cfg, PAPER_CLIENTS, PAPER_CUTS, train, test,
                               run, server=TPU_V5E)
    sim.client_losses = {}
    return sim


PHASES = {
    "seq": {},
    "batched": {"cohort_chunk": 6},
    "kernels": {"cohort_chunk": 6, "cohort_impl": "ragged",
                "fused_lora": True},
}


def run_phase(name: str, cfg, train, test, batch: int) -> dict:
    """One phase: build, train ``ROUNDS`` rounds, evaluate, check, report."""
    import jax
    from repro.fed import EngineConfig, FedRunConfig

    run = FedRunConfig(scheme="ours", batch_size=batch,
                       seq_len=int(train.tokens.shape[1]), rounds=ROUNDS,
                       eval_every=ROUNDS, seed=SEED,
                       engine=EngineConfig(**PHASES[name]))
    with CompileClock() as clock:
        t0 = time.perf_counter()
        sim = build_simulator(cfg, train, test, run)
        jax.block_until_ready((sim.params, sim.server_lora, sim.client_lora))
        t_setup = time.perf_counter() - t0
        history = sim.run_training()
        acc, f1 = sim.evaluate()
        jax.block_until_ready((sim.server_lora, sim.client_lora, sim.heads))
        wall = time.perf_counter() - t0

    n_clients = len(sim.cuts)
    losses = sim.client_losses
    _require(len(history) == ROUNDS, f"{name}: {len(history)} rounds ran")
    _require(sorted(losses) == [(r, u) for r in range(ROUNDS)
                                for u in range(n_clients)],
             f"{name}: served {sorted(losses)}")
    _require(all(math.isfinite(v) for v in losses.values())
             and all(math.isfinite(h.mean_loss) for h in history),
             f"{name}: non-finite loss {losses}")
    for a in (acc, history[-1].accuracy):
        _require(a is not None and 0.0 <= a <= 1.0,
                 f"{name}: accuracy {a} is not in [0, 1]")
    stats = jax.devices()[0].memory_stats() or {}
    compile_s = clock.seconds()
    rec = {
        "phase": name,
        "setup_s": t_setup,
        "wall_s": wall,
        "compile_s": compile_s,
        "n_compiles": clock.n_compiles,
        "wall_minus_compile_s": wall - compile_s,
        "round_mean_loss": [h.mean_loss for h in history],
        "client_loss_round0": [losses[(0, u)] for u in range(n_clients)],
        "client_loss_round1": [losses[(1, u)] for u in range(n_clients)],
        "accuracy": acc,
        "f1": f1,
        # the device's high-water mark since this process started
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        # the cost model's clock (Eq. 10-12 with the assumed TPU_V5E server
        # profile): simulated seconds, not a time measured on the device
        "sim_clock_simulated_s": sim.sim_clock,
    }
    if name == "kernels":
        rec["tpu_custom_call_ops"] = assert_kernels_compiled(
            cfg, rows=6 * batch * run.seq_len)
    print(json.dumps(rec), flush=True)
    del sim
    gc.collect()
    return rec


def compare_round0(a: dict, b: dict) -> float:
    """Largest relative difference of two phases' first-round per-client
    losses; fails beyond LOSS_RTOL."""
    worst = max(abs(x - y) / abs(y) for x, y in
                zip(a["client_loss_round0"], b["client_loss_round0"]))
    print(json.dumps({"compare": f"{a['phase']} vs {b['phase']}",
                      "max_rel_diff_round0": worst, "rtol": LOSS_RTOL}),
          flush=True)
    _require(worst <= LOSS_RTOL,
             f"{a['phase']} vs {b['phase']}: first-round losses differ by "
             f"{worst:.3g} relative (> {LOSS_RTOL})")
    return worst


def run_phases(cfg, train, test, batch: int = BATCH) -> list:
    """Every phase, then the cross-phase loss checks."""
    recs = [run_phase(name, cfg, train, test, batch) for name in PHASES]
    seq, batched, kernels = recs
    compare_round0(batched, seq)
    compare_round0(kernels, batched)
    return recs


def main() -> None:
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"chip_smoke: no repro package under {ROOT / 'src'}; run "
                 f"this script from a checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache(ROOT)   # before anything compiles

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found {dev.platform} "
                 f"({dev.device_kind})")
    print(json.dumps({"device": str(dev), "kind": dev.device_kind,
                      "jax": jax.__version__, "compile_cache": cache_dir}),
          flush=True)

    from repro.configs import REGISTRY
    from repro.data import make_emotion_dataset
    cfg = REGISTRY["bert-base"]
    train = make_emotion_dataset(N_TRAIN, seq_len=128,
                                 vocab_size=cfg.vocab_size, seed=SEED)
    test = make_emotion_dataset(N_TEST, seq_len=128,
                                vocab_size=cfg.vocab_size, seed=SEED + 1)
    recs = run_phases(cfg, train, test)
    _require(all(r["peak_bytes_in_use"] for r in recs),
             "the device reported no peak_bytes_in_use")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
