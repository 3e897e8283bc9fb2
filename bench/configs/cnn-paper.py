"""Plain reference of ``cnn-paper`` and its operation count.

Two 3x3 convolutions (SAME padding, bias, ReLU, 2x2 max-pool; written as
matmuls over shifted copies of the input) and two fully
connected layers (ReLU between), as in the paper's Sec 5.1.  The weights are
drawn from the seed as the program draws them (a normal per parameter, keyed
by the FNV-1a hash of its path, scale 1.4/sqrt(fan-in) for the convolutions
and 1/sqrt(fan-in) otherwise; zero biases).  Nothing here imports the
program.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp

CONFIG = json.loads(Path(__file__).with_suffix(".json").read_text())
_M = CONFIG["model"]


def _fnv1a(s: str) -> int:
    h = 2166136261
    for ch in s.encode():
        h = ((h ^ ch) * 16777619) & 0xFFFFFFFF
    return h


def _normal(key, path, shape, fan_in, scale=1.0):
    k = jax.random.fold_in(key, _fnv1a(path))
    return jax.random.normal(k, shape, jnp.float32) * (scale / math.sqrt(fan_in))


def init(seed: int):
    """float32 weights of the stage seeded by ``seed``."""
    key = jax.random.key(seed)
    c1, c2 = _M["cnn_channels"]
    cin, hid, out = _M["image_channels"], _M["d_model"], _M["num_classes"]
    flat = (_M["image_size"] // 4) ** 2 * c2
    z = lambda n: jnp.zeros((n,), jnp.float32)  # noqa: E731
    return {
        "conv1": _normal(key, "cnn/conv1", (3, 3, cin, c1), 9 * cin, 1.4),
        "b1": z(c1),
        "conv2": _normal(key, "cnn/conv2", (3, 3, c1, c2), 9 * c1, 1.4),
        "b2": z(c2),
        "fc1": _normal(key, "cnn/fc1", (flat, hid), flat),
        "fb1": z(hid),
        "fc2": _normal(key, "cnn/fc2", (hid, out), hid),
        "fb2": z(out),
    }


def _conv3x3(x, w):
    """3x3 cross-correlation, stride 1, SAME padding, as one matmul over the
    nine shifted copies of the input (so that clients batched by vmap make a
    batched matmul, not a grouped convolution)."""
    n, h, wd, c = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    cols = jnp.concatenate([xp[:, i:i + h, j:j + wd, :]
                            for i in range(3) for j in range(3)], axis=-1)
    return cols @ w.reshape(9 * c, w.shape[-1])


def _conv_relu_pool(x, w, b):
    y = jax.nn.relu(_conv3x3(x, w) + b)
    n, hh, ww, c = y.shape
    return y.reshape(n, hh // 2, 2, ww // 2, 2, c).max(axis=(2, 4))


def logits(p, images):
    x = _conv_relu_pool(images.astype(p["conv1"].dtype), p["conv1"], p["b1"])
    x = _conv_relu_pool(x, p["conv2"], p["b2"])
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ p["fc1"] + p["fb1"])
    return x @ p["fc2"] + p["fb2"]


def loss(p, images, labels):
    """Mean cross-entropy over the batch."""
    lg = logits(p, images)
    ll = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(ll, labels[:, None], axis=-1))


def flops_per_example() -> float:
    """Forward plus backward (3x forward) of one image: both convolutions at
    full resolution before their pools, and both fully connected layers."""
    s, cin = _M["image_size"], _M["image_channels"]
    c1, c2 = _M["cnn_channels"]
    conv1 = 2 * s * s * c1 * 9 * cin
    conv2 = 2 * (s // 2) ** 2 * c2 * 9 * c1
    fc = 2 * (s // 4) ** 2 * c2 * _M["d_model"] + 2 * _M["d_model"] * _M["num_classes"]
    return 3.0 * (conv1 + conv2 + fc)


def make_clients(seed: int, num_clients: int, samples: int):
    from bench.harness.data import image_clients
    return image_clients(seed, num_clients, samples, _M["image_size"],
                         _M["image_channels"], _M["num_classes"],
                         noise=CONFIG["data"]["noise"])
