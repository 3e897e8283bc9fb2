"""Client data made from the seed: a Zipfian character stream for the
generation task (in place of Tiny Shakespeare) and class-conditional images
for the classification task (in place of MNIST).

Both are made in bulk with NumPy on the host, and every seed gives the same
sizes: ``num_clients`` clients of ``samples`` examples each.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

Clients = Dict[int, Tuple[np.ndarray, np.ndarray]]


def char_clients(seed: int, num_clients: int, samples: int, seq_len: int,
                 vocab: int, n_words: int = 400) -> Clients:
    """Next-token pairs ``(tokens, labels)``, each ``(samples, seq_len)``
    int32, cut from one stream of words (2 to 8 symbols from ``1..vocab-1``,
    Zipf-weighted, separated by symbol 0)."""
    rng = np.random.default_rng(seed)
    lengths = rng.integers(2, 9, n_words)
    words = [rng.integers(1, vocab, n).astype(np.int32) for n in lengths]
    probs = 1.0 / np.arange(1, n_words + 1)
    probs /= probs.sum()
    need = num_clients * samples * seq_len + 1
    picks = rng.choice(n_words, size=need // 3 + 16, p=probs)
    stream = np.concatenate([np.append(words[i], np.int32(0))
                             for i in picks])
    while stream.size < need:
        stream = np.concatenate([stream, stream])
    stream = stream[:need]
    toks = stream[:-1].reshape(num_clients, samples, seq_len)
    labs = stream[1:].reshape(num_clients, samples, seq_len)
    return {k: (toks[k], labs[k]) for k in range(num_clients)}


def image_clients(seed: int, num_clients: int, samples: int, size: int,
                  channels: int, classes: int, noise: float = 0.25,
                  proto_seed: int = 1234) -> Clients:
    """``(images (samples, size, size, channels) float32 in [0, 1], labels
    (samples,) int32)``: smooth class prototypes (fixed by ``proto_seed``, so
    every seed draws from one distribution) plus pixel noise."""
    proto_rng = np.random.default_rng(proto_seed)
    yy, xx = np.mgrid[0:size, 0:size] / size
    protos = np.zeros((classes, size, size, channels), np.float32)
    for c in range(classes):
        for ch in range(channels):
            for _ in range(3):
                fx, fy = proto_rng.uniform(1, 4, 2)
                ph = proto_rng.uniform(0, 2 * np.pi, 2)
                protos[c, :, :, ch] += (np.sin(2 * np.pi * fx * xx + ph[0])
                                        * np.sin(2 * np.pi * fy * yy + ph[1]))
    protos = (protos - protos.min()) / (np.ptp(protos) + 1e-9)
    rng = np.random.default_rng(seed)
    n = num_clients * samples
    labels = rng.integers(0, classes, n).astype(np.int32)
    images = protos[labels] + noise * rng.standard_normal(
        (n, size, size, channels), dtype=np.float32)
    images = np.clip(images, 0.0, 1.0).astype(np.float32)
    images = images.reshape(num_clients, samples, size, size, channels)
    labels = labels.reshape(num_clients, samples)
    return {k: (images[k], labels[k]) for k in range(num_clients)}
