"""Drives the system under test: one ``repro.fed.Simulator`` built from a
configuration and a traffic file, warmed up, then run in whole rounds.

The benchmark chooses no implementation: ``EngineConfig`` keeps the
program's defaults, so a change to how the program picks its path is
measured on the same traffic.  During the first rounds a ``Recorder``
reads, without changing anything, what the correctness check compares:
each client's loss, the batches the loaders handed out, the optimizer's
state after the first step and the trainables after the checked rounds.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

# the configuration file's published sizes and the program's ModelConfig
# field that must hold each of them
_PROGRAM_FIELDS = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
                   "num_attention_heads": "n_heads",
                   "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
                   "intermediate_size": "d_ff", "vocab_size": "vocab_size",
                   "num_labels": "n_classes", "torch_dtype": "dtype",
                   "causal": "causal"}


def program_config(c: dict):
    """The program's ModelConfig for configuration file ``c``; refuses one
    whose sizes are not the file's."""
    from repro.configs import REGISTRY
    prog = c["program"]
    cfg = REGISTRY[prog["registry"]].with_(**prog.get("with", {}))
    if "lora" in prog:
        cfg = cfg.with_(lora=dataclasses.replace(cfg.lora, **prog["lora"]))
    wrong = {key: (c[key], getattr(cfg, field))
             for key, field in _PROGRAM_FIELDS.items()
             if c[key] != getattr(cfg, field)}
    lora = c["lora"]
    for key, have in (("rank", cfg.lora.rank), ("alpha", cfg.lora.alpha),
                      ("targets", list(cfg.lora.targets))):
        if lora[key] != have:
            wrong[f"lora.{key}"] = (lora[key], have)
    if wrong:
        raise ValueError(f"{c['name']}: the program's config differs from the "
                         f"file (file, program): {wrong}")
    return cfg


class Recorder:
    """What the first ``rounds`` rounds did, read from the simulator."""

    def __init__(self, rounds: int):
        self.rounds = rounds
        self.round = 0
        self.losses: dict = {}      # (round, uid) -> loss
        self.batches: dict = {}     # (round, uid) -> (tokens, labels)
        self.grad1 = None           # per-leaf norms after the first step
        self.change = None          # per-leaf norms of the checked change
        self.start = None           # trainables before the first round

    @property
    def on(self) -> bool:
        return self.round < self.rounds


def _trainables(sim, opt_mu: bool = False) -> list:
    """Every client's ``{"lora": {proj: {a, b}: (L, ...)}, "head"}``: its
    adapters (or, with ``opt_mu``, its optimizer's first moment), the
    client's layers [0, cut) joined to the server's [cut, L)."""
    import jax
    import jax.numpy as jnp
    out = []
    for u, cut in enumerate(sim.cuts):
        if opt_mu:
            c_tree = sim.client_opt[u].mu["layers"]
            s_tree = sim.server_opt[u].mu["lora"]["layers"]
            head = sim.server_opt[u].mu["head"]
        else:
            c_tree = sim.client_lora[u]["layers"]
            s_tree = sim.server_lora[u]["layers"]
            head = sim.heads[u]
        full = jax.tree.map(
            lambda a, b, cut=cut: jnp.concatenate([a[:cut], b[cut:]]),
            c_tree, s_tree)
        # {"attn": {"wq": {a, b}, ...}, "mlp": {...}} -> {"wq": {a, b}, ...}
        out.append({"lora": {p: ad for part in full.values()
                             for p, ad in part.items()}, "head": head})
    return out


def build(c: dict, traffic: dict, seed: int, recorder: Recorder):
    """The simulator of one run, with ``recorder`` reading its first rounds."""
    import jax
    from repro.data import EmotionDataset
    from repro.fed import Simulator, AggConfig, FedRunConfig
    from repro import fed

    from bench.traffic import emotion_corpus

    cfg = program_config(c)
    tokens, labels = emotion_corpus(traffic["corpus"], traffic["n_train"],
                                    traffic["seq_len"], c["vocab_size"], seed)
    run = FedRunConfig(scheme="ours", batch_size=traffic["batch"],
                       seq_len=traffic["seq_len"], lr=traffic["lr"],
                       alpha=traffic["alpha"], rounds=1 << 30,
                       eval_every=1 << 30, seed=seed,
                       agg=AggConfig(interval=traffic["agg_interval"]))
    devices = getattr(fed, traffic["fleet"])
    rec = recorder

    class BenchSimulator(Simulator):
        def _serve_group(self, grp):
            losses = super()._serve_group(grp)
            if rec.on:
                rec.losses.update({(rec.round, u): float(v)
                                   for u, v in zip(grp, losses)})
            return losses

        def _round_parallel(self):
            out = super()._round_parallel()
            if rec.on and rec.round == 0:   # before any commit resets it
                b1 = self.opt.b1
                rec.grad1 = _norms_host(
                    [jax.tree.map(lambda m: m / (1 - b1), t)
                     for t in _trainables(self, True)])
            return out

    sim = BenchSimulator(cfg, list(devices), traffic["cuts"],
                         EmotionDataset(tokens, labels), None, run)
    adam = traffic["adam"]
    have = {"b1": sim.opt.b1, "b2": sim.opt.b2, "eps": sim.opt.eps,
            "lr": sim.opt.learning_rate}
    want = dict(adam, lr=traffic["lr"])
    if have != want or sim.opt.weight_decay:
        raise ValueError(f"the program's optimizer {have} is not the "
                         f"traffic's {want}")
    rec.start = _trainables(sim)
    for u, loader in enumerate(sim.loaders):
        loader.next_batch = _recording(loader.next_batch, rec, u)
    return sim


def _recording(next_batch, rec: Recorder, uid: int):
    def wrapped():
        batch = next_batch()
        if rec.on:
            rec.batches[(rec.round, uid)] = (np.array(batch["tokens"]),
                                             np.array(batch["label"]))
        return batch
    return wrapped


def _norms_host(tree) -> dict:
    from bench.reference.replay import leaf_norms
    return leaf_norms(tree)


def warm_up(sim, rec: Recorder, n_rounds: int) -> None:
    """Run ``n_rounds`` whole rounds through the window's own call; the
    recorder reads the first ``rec.rounds`` of them."""
    import jax
    for r in range(n_rounds):
        sim.run_round(r)
        rec.round = r + 1
        if rec.round == rec.rounds:
            rec.change = _norms_host(
                [jax.tree.map(lambda a, b: a - b, now, start)
                 for now, start in zip(_trainables(sim), rec.start)])
            rec.start = None
    jax.block_until_ready((sim.client_lora, sim.server_lora, sim.heads))


def window(sim, first_round: int, seconds: float, period: int,
           after_round=None):
    """Run whole aggregation periods of ``period`` rounds until ``seconds``
    have passed, so every window holds its share of commits; returns the
    round count, the window's wall seconds (to the end of the last round's
    device work) and each round's host seconds.  ``after_round(n,
    elapsed_s)``, if given, is called after the n-th round."""
    import jax
    n, durations = 0, []
    t0 = time.perf_counter()
    while True:
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.round"):
            sim.run_round(first_round + n)
        now = time.perf_counter()
        durations.append(now - t)
        n += 1
        if after_round is not None:
            after_round(n, now - t0)
        if n % period == 0 and now - t0 >= seconds:
            break
    jax.block_until_ready((sim.client_lora, sim.server_lora, sim.heads))
    return n, time.perf_counter() - t0, durations


def recorded(rec: Recorder, sim) -> dict | None:
    """The program's side of the check, or None where the recorder missed
    part of it (then nothing can be compared and the run is not correct)."""
    n, rounds = len(sim.cuts), rec.rounds
    want = [(r, u) for r in range(rounds) for u in range(n)]
    shape = (sim.run.batch_size, sim.run.seq_len)
    if (sorted(rec.losses) != want or sorted(rec.batches) != want
            or rec.grad1 is None or rec.change is None
            or any(rec.batches[k][0].shape != shape for k in want)):
        return None
    return {"loss": np.array([[rec.losses[(r, u)] for u in range(n)]
                              for r in range(rounds)]),
            "grad1": rec.grad1, "change": rec.change,
            "batches": [[rec.batches[(r, u)] for u in range(n)]
                        for r in range(rounds)]}
