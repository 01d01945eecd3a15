"""The one generator of training traffic: a seeded emotion corpus and the
federated run that serves it, both read from a traffic file.

A traffic file (``bench/traffic/<name>.json``) holds parameters only:

``fleet``/``cuts``    which client devices the round clock prices, and the
                      layers each runs before its activations go up;
``batch``/``seq_len`` sequences and tokens per client per round;
``n_train``/``alpha`` corpus size and the Dirichlet concentration of its
                      non-IID split over clients;
``agg_interval``      rounds between sync commits (FedAvg of the adapters);
``lr``/``adam``       the optimizer the clients and the server train with;
``corpus``            the token statistics of the corpus (below).

The corpus generator is a copy of the program's ``make_emotion_dataset``
(``repro/data/synthetic.py``), kept here so that no change to the program
changes what the benchmark feeds it: each class has a band of
characteristic tokens mixed with a shared common band, plus a class-specific
bigram, and every row starts with a [CLS]-like id and ends in padding.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np


def load_traffic(path: Path) -> dict:
    spec = json.loads(Path(path).read_text())
    for key in ("fleet", "cuts", "batch", "seq_len", "n_train", "alpha",
                "agg_interval", "lr", "adam", "corpus"):
        if key not in spec:
            raise KeyError(f"traffic file {path} has no {key!r}")
    return spec


def emotion_corpus(corpus: dict, n: int, seq_len: int, vocab_size: int,
                   seed: int) -> tuple[np.ndarray, np.ndarray]:
    """``(tokens (n, seq_len) int32, labels (n,) int32)`` drawn from
    ``seed``; the same seed gives the same rows."""
    rng = np.random.default_rng(seed)
    skew = np.asarray(corpus["class_skew"], np.float64)
    n_classes = len(skew)
    labels = rng.choice(n_classes, size=n, p=skew / skew.sum())
    band = int(corpus["band"])
    common_lo = n_classes * band + 10
    common_hi = min(vocab_size, common_lo + int(corpus["common_width"]))
    if common_hi <= common_lo:
        raise ValueError(f"vocabulary of {vocab_size} leaves no common band "
                         f"above token {common_lo}")
    tokens = np.empty((n, seq_len), np.int32)
    for c in range(n_classes):
        idx = np.where(labels == c)[0]
        if idx.size == 0:
            continue
        k = idx.size
        lengths = rng.integers(int(corpus["min_len"]), seq_len, size=k)
        is_class = rng.random((k, seq_len)) < float(corpus["class_share"])
        class_band = rng.integers(10 + c * band, 10 + (c + 1) * band,
                                  size=(k, seq_len))
        common = rng.integers(common_lo, common_hi, size=(k, seq_len))
        seqs = np.where(is_class, class_band, common).astype(np.int32)
        # bigram signal: a class-band token is followed by (t + c) mod band
        seqs[:, 1:] = np.where(
            is_class[:, :-1],
            10 + c * band + (seqs[:, :-1] - 10 - c * band + c + 1) % band,
            seqs[:, 1:])
        seqs[np.arange(seq_len)[None, :] >= lengths[:, None]] = 0   # pad
        seqs[:, 0] = 1                                              # [CLS]
        tokens[idx] = seqs
    return tokens, labels.astype(np.int32)
