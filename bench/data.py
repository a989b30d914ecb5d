"""Synthetic MNIST-shaped federated datasets, made by the benchmark.

A copy of the program's generator (class prototypes from a low-frequency
Fourier mixture, samples ``clip(proto + sigma * eps, 0, 1)``, standardized)
and of its one-class-per-device partition, so that the engine under test
and the plain reference are fed data that neither of them made. The same
parameters give the same arrays as the program's own generator.
"""
from __future__ import annotations

import numpy as np


def _prototype(rng: np.random.Generator, shape) -> np.ndarray:
    h, w = shape[0], shape[1]
    c = shape[2] if len(shape) > 2 else 1
    yy, xx = np.meshgrid(np.linspace(0, 1, h), np.linspace(0, 1, w),
                         indexing="ij")
    img = np.zeros((h, w, c))
    for ch in range(c):
        acc = np.zeros((h, w))
        for _ in range(6):
            fy, fx = rng.integers(1, 4, size=2)
            phase = rng.uniform(0, 2 * np.pi, size=2)
            amp = rng.uniform(0.4, 1.0)
            acc += amp * np.sin(2 * np.pi * fy * yy + phase[0]) \
                * np.cos(2 * np.pi * fx * xx + phase[1])
        acc = (acc - acc.min()) / (acc.max() - acc.min() + 1e-9)
        img[..., ch] = acc
    return img


def classification_dataset(*, image_shape, n_classes: int,
                           n_train_per_class: int, n_test_per_class: int,
                           noise_sigma: float, seed: int):
    """(x_train, y_train, x_test, y_test); images flattened, standardized."""
    rng = np.random.default_rng(seed)
    shape = tuple(image_shape)
    protos = [_prototype(rng, shape) for _ in range(n_classes)]

    def sample(n_per_class):
        xs, ys = [], []
        for cls in range(n_classes):
            eps = rng.normal(size=(n_per_class,) + shape)
            x = np.clip(protos[cls][None] + noise_sigma * eps, 0.0, 1.0)
            xs.append(x.reshape(n_per_class, -1))
            ys.append(np.full(n_per_class, cls, dtype=np.int64))
        x = np.concatenate(xs).astype(np.float32)
        y = np.concatenate(ys)
        perm = rng.permutation(x.shape[0])
        return x[perm], y[perm]

    x_tr, y_tr = sample(n_train_per_class)
    x_te, y_te = sample(n_test_per_class)
    mean, std = x_tr.mean(0, keepdims=True), x_tr.std(0, keepdims=True) + 1e-6
    return (x_tr - mean) / std, y_tr, (x_te - mean) / std, y_te


def partition_by_class(x, y, n_devices: int, classes_per_device: int,
                       samples_per_device: int, seed: int):
    """Per-device (x, y) shards drawn from round-robin assigned classes."""
    rng = np.random.default_rng(seed)
    n_classes = int(y.max()) + 1
    idx_by_class = [np.flatnonzero(y == c) for c in range(n_classes)]
    for idx in idx_by_class:
        rng.shuffle(idx)
    cursors = [0] * n_classes
    per_cls = samples_per_device // classes_per_device
    shards = []
    for m in range(n_devices):
        xs, ys = [], []
        for j in range(classes_per_device):
            c = (m * classes_per_device + j) % n_classes
            idx = idx_by_class[c]
            take = idx[cursors[c]:cursors[c] + per_cls]
            if take.shape[0] < per_cls:
                cursors[c] = 0
                take = idx[:per_cls]
            cursors[c] += per_cls
            xs.append(x[take])
            ys.append(y[take])
        shards.append((np.concatenate(xs), np.concatenate(ys)))
    return shards


def federated_dataset(spec: dict, n_devices: int):
    """Stacked device data ``(xs (N, n, f) f32, ys (N, n) i32)`` and the
    test split, from a config's ``data`` block."""
    x_tr, y_tr, x_te, y_te = classification_dataset(
        image_shape=spec["image_shape"], n_classes=spec["n_classes"],
        n_train_per_class=spec["n_train_per_class"],
        n_test_per_class=spec["n_test_per_class"],
        noise_sigma=spec["noise_sigma"], seed=spec["dataset_seed"])
    shards = partition_by_class(x_tr, y_tr, n_devices,
                                spec["classes_per_device"],
                                spec["samples_per_device"],
                                spec["partition_seed"])
    xs = np.stack([s[0] for s in shards]).astype(np.float32)
    ys = np.stack([s[1] for s in shards]).astype(np.int32)
    return xs, ys, x_te.astype(np.float32), y_te.astype(np.int32)
