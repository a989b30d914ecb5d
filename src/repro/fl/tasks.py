"""Learning tasks for the FL simulation experiments (Sec. V).

Every task exposes a *flat-vector* parameter interface (the aggregators in
``core.baselines`` operate on d-dimensional numpy gradients, mirroring the
paper's w in R^d):

  init_params() -> np.ndarray (d,)
  device_grads(w, xs, ys)  -> (losses (N,), grads (N, d))   [vmapped, jit]
  global_loss(w, x, y)     -> float   (the global objective F(w))
  accuracy(w, x, y)        -> float

Tasks:
  * SoftmaxRegressionTask — l2-regularized softmax regression; mu-strongly
    convex, L = 2 + mu smooth (paper Sec. V-A, [17]). d = C*(features+1).
  * MLPTask — one-hidden-layer MLP with l2 regularization (the smooth
    non-convex task standing in for ResNet-18 at CPU scale; Sec. V-B).

Assumption 1 (||g|| <= G_max) is enforced the standard way, by clipping the
per-device stochastic gradient to norm G_max (cf. [34] in the paper).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp


def jit_f32(fn):
    """``jax.jit(fn)`` with every matmul traced at full f32 precision.

    A TPU runs a default-precision f32 matmul as one bf16 pass. The f32
    dtype contract -- and the engine's parity with the f64 oracle, whose
    gradients come from these same programs -- needs the full product.
    No effect on CPU.
    """
    def traced(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    return jax.jit(traced)


def _clip_to(g: jnp.ndarray, g_max: float) -> jnp.ndarray:
    nrm = jnp.linalg.norm(g)
    return g * jnp.minimum(1.0, g_max / jnp.maximum(nrm, 1e-12))


def _device_grad_at(device_grad):
    """Mini-batch view of a per-device gradient: gather the batch rows by
    index inside the jit, then run the same clipped-gradient program. Both
    simulation backends call this one compiled function (vmapped over the
    device axis, gradients vmapped over the gathered batch axis), so their
    stochastic gradients are bit-identical given identical indices."""
    def grad_at(w_flat, x, y, idx):
        return device_grad(w_flat, x[idx], y[idx])
    return grad_at


def _device_grad_at_weighted(device_grad_w):
    """Weighted-gather view for the *mixed* full/mini-batch regime: gather
    ``batch_size`` rows by index, then a clipped gradient of the
    *weighted-sum* loss. With weights 1/n_m on a full device's n_m real
    rows (0 on the clipped duplicates) or 1/B on a mini device's B drawn
    rows, this equals the mean-loss gradient up to fp summation order."""
    def grad_at(w_flat, x, y, idx, wt):
        return device_grad_w(w_flat, x[idx], y[idx], wt)
    return grad_at


class SoftmaxRegressionTask:
    """phi(w,(x,l)) = mu/2 ||w||^2 - log softmax_l(x^T W); strongly convex."""

    def __init__(self, n_features: int, n_classes: int = 10, mu: float = 0.01,
                 g_max: float = 20.0):
        self.n_features = n_features
        self.n_classes = n_classes
        self.mu = mu
        self.smooth_l = 2.0 + mu
        self.g_max = g_max
        self.dim = n_classes * (n_features + 1)

        def loss(w_flat, x, y):
            W = w_flat.reshape(n_classes, n_features + 1)
            logits = x @ W[:, :-1].T + W[:, -1]
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.mean(logp[jnp.arange(x.shape[0]), y])
            return nll + 0.5 * mu * jnp.sum(w_flat ** 2)

        self._loss = jit_f32(loss)
        grad1 = jax.grad(loss)

        def device_grad(w_flat, x, y):
            return _clip_to(grad1(w_flat, x, y), g_max)

        self._device_grads = jit_f32(
            jax.vmap(device_grad, in_axes=(None, 0, 0)))
        self._device_losses = jit_f32(jax.vmap(loss, in_axes=(None, 0, 0)))
        self._device_grads_at = jit_f32(
            jax.vmap(_device_grad_at(device_grad), in_axes=(None, 0, 0, 0)))

        def loss_w(w_flat, x, y, wt):
            W = w_flat.reshape(n_classes, n_features + 1)
            logits = x @ W[:, :-1].T + W[:, -1]
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -(wt * logp[jnp.arange(x.shape[0]), y]).sum()
            return nll + 0.5 * mu * jnp.sum(w_flat ** 2)

        grad1_w = jax.grad(loss_w)

        def device_grad_w(w_flat, x, y, wt):
            return _clip_to(grad1_w(w_flat, x, y, wt), g_max)

        self._device_grads_at_w = jit_f32(
            jax.vmap(_device_grad_at_weighted(device_grad_w),
                     in_axes=(None, 0, 0, 0, 0)))

        def acc(w_flat, x, y):
            W = w_flat.reshape(n_classes, n_features + 1)
            logits = x @ W[:, :-1].T + W[:, -1]
            return jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))

        self._acc = jit_f32(acc)

    def init_params(self, seed: int = 0) -> np.ndarray:
        return np.zeros(self.dim, dtype=np.float64)

    @property
    def loss_fn(self):
        """Jitted pure loss (w32, x, y) -> scalar, for jit/vmap composition."""
        return self._loss

    @property
    def accuracy_fn(self):
        """Jitted pure accuracy (w32, x, y) -> scalar."""
        return self._acc

    @property
    def device_grads_fn(self):
        """Jitted vmapped per-device clipped gradient (w32, xs, ys) -> (N,d)."""
        return self._device_grads

    @property
    def device_grads_at_fn(self):
        """Jitted mini-batch gradient (w32, xs (N,n,f), ys, idx (N,B)) ->
        (N,d): gathers each device's batch by index, then the clipped grad."""
        return self._device_grads_at

    @property
    def device_grads_at_weighted_fn(self):
        """Jitted weighted mini-batch gradient for the mixed full/mini
        regime: (w32, xs, ys, idx (N,B), wt (N,B)) -> (N,d). Per-row
        weights replace the mean so full devices (weight 1/n_m on real
        rows, 0 on duplicates) and mini devices (1/B) share one program."""
        return self._device_grads_at_w

    def device_grads(self, w, xs, ys):
        """xs: (N, n, feat), ys: (N, n) stacked device batches."""
        g = self._device_grads(jnp.asarray(w, jnp.float32),
                               jnp.asarray(xs), jnp.asarray(ys))
        return np.asarray(g, dtype=np.float64)

    def device_grads_at(self, w, xs, ys, idx):
        """Mini-batch gradients on stacked full data + (N, B) indices."""
        g = self._device_grads_at(jnp.asarray(w, jnp.float32),
                                  jnp.asarray(xs), jnp.asarray(ys),
                                  jnp.asarray(idx))
        return np.asarray(g, dtype=np.float64)

    def device_losses(self, w, xs, ys):
        return np.asarray(self._device_losses(jnp.asarray(w, jnp.float32),
                                              jnp.asarray(xs), jnp.asarray(ys)))

    def global_loss(self, w, x, y) -> float:
        return float(self._loss(jnp.asarray(w, jnp.float32),
                                jnp.asarray(x), jnp.asarray(y)))

    def accuracy(self, w, x, y) -> float:
        return float(self._acc(jnp.asarray(w, jnp.float32),
                               jnp.asarray(x), jnp.asarray(y)))

    def grad_norm_at_zero(self, xs, ys) -> np.ndarray:
        """||grad f_m(0)|| per device — for the projection radius D."""
        g = self.device_grads(np.zeros(self.dim), xs, ys)
        return np.linalg.norm(g, axis=1)


class MLPTask:
    """One-hidden-layer MLP + l2 reg: smooth non-convex task (Sec. V-B)."""

    def __init__(self, n_features: int, hidden: int = 64, n_classes: int = 10,
                 mu_nc: float = 0.01, g_max: float = 49.0, seed: int = 0):
        self.n_features, self.hidden, self.n_classes = n_features, hidden, n_classes
        self.mu_nc, self.g_max = mu_nc, g_max
        self.dim = (n_features * hidden + hidden) + (hidden * n_classes + n_classes)
        self._seed = seed

        def unpack(w):
            i = 0
            W1 = w[i:i + n_features * hidden].reshape(n_features, hidden)
            i += n_features * hidden
            b1 = w[i:i + hidden]; i += hidden
            W2 = w[i:i + hidden * n_classes].reshape(hidden, n_classes)
            i += hidden * n_classes
            b2 = w[i:i + n_classes]
            return W1, b1, W2, b2

        def loss(w_flat, x, y):
            W1, b1, W2, b2 = unpack(w_flat)
            hdn = jax.nn.relu(x @ W1 + b1)
            logits = hdn @ W2 + b2
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.mean(logp[jnp.arange(x.shape[0]), y])
            return nll + 0.5 * mu_nc * jnp.sum(w_flat ** 2)

        self._loss = jit_f32(loss)
        grad1 = jax.grad(loss)

        def device_grad(w_flat, x, y):
            return _clip_to(grad1(w_flat, x, y), g_max)

        self._device_grads = jit_f32(
            jax.vmap(device_grad, in_axes=(None, 0, 0)))
        self._device_grads_at = jit_f32(
            jax.vmap(_device_grad_at(device_grad), in_axes=(None, 0, 0, 0)))

        def loss_w(w_flat, x, y, wt):
            W1, b1, W2, b2 = unpack(w_flat)
            hdn = jax.nn.relu(x @ W1 + b1)
            logits = hdn @ W2 + b2
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -(wt * logp[jnp.arange(x.shape[0]), y]).sum()
            return nll + 0.5 * mu_nc * jnp.sum(w_flat ** 2)

        grad1_w = jax.grad(loss_w)

        def device_grad_w(w_flat, x, y, wt):
            return _clip_to(grad1_w(w_flat, x, y, wt), g_max)

        self._device_grads_at_w = jit_f32(
            jax.vmap(_device_grad_at_weighted(device_grad_w),
                     in_axes=(None, 0, 0, 0, 0)))

        def acc(w_flat, x, y):
            W1, b1, W2, b2 = unpack(w_flat)
            logits = jax.nn.relu(x @ W1 + b1) @ W2 + b2
            return jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))

        self._acc = jit_f32(acc)
        self._unpack = unpack

    def init_params(self, seed: Optional[int] = None) -> np.ndarray:
        rng = np.random.default_rng(self._seed if seed is None else seed)
        w = np.zeros(self.dim)
        w1 = rng.normal(scale=np.sqrt(2.0 / self.n_features),
                        size=self.n_features * self.hidden)
        w2 = rng.normal(scale=np.sqrt(2.0 / self.hidden),
                        size=self.hidden * self.n_classes)
        w[:w1.shape[0]] = w1
        w[self.n_features * self.hidden + self.hidden:
          self.n_features * self.hidden + self.hidden + w2.shape[0]] = w2
        return w

    @property
    def loss_fn(self):
        """Jitted pure loss (w32, x, y) -> scalar, for jit/vmap composition."""
        return self._loss

    @property
    def accuracy_fn(self):
        """Jitted pure accuracy (w32, x, y) -> scalar."""
        return self._acc

    @property
    def device_grads_fn(self):
        """Jitted vmapped per-device clipped gradient (w32, xs, ys) -> (N,d)."""
        return self._device_grads

    @property
    def device_grads_at_fn(self):
        """Jitted mini-batch gradient (w32, xs (N,n,f), ys, idx (N,B)) ->
        (N,d): gathers each device's batch by index, then the clipped grad."""
        return self._device_grads_at

    @property
    def device_grads_at_weighted_fn(self):
        """Jitted weighted mini-batch gradient for the mixed full/mini
        regime: (w32, xs, ys, idx (N,B), wt (N,B)) -> (N,d). Per-row
        weights replace the mean so full devices (weight 1/n_m on real
        rows, 0 on duplicates) and mini devices (1/B) share one program."""
        return self._device_grads_at_w

    def device_grads(self, w, xs, ys):
        g = self._device_grads(jnp.asarray(w, jnp.float32),
                               jnp.asarray(xs), jnp.asarray(ys))
        return np.asarray(g, dtype=np.float64)

    def device_grads_at(self, w, xs, ys, idx):
        """Mini-batch gradients on stacked full data + (N, B) indices."""
        g = self._device_grads_at(jnp.asarray(w, jnp.float32),
                                  jnp.asarray(xs), jnp.asarray(ys),
                                  jnp.asarray(idx))
        return np.asarray(g, dtype=np.float64)

    def global_loss(self, w, x, y) -> float:
        return float(self._loss(jnp.asarray(w, jnp.float32),
                                jnp.asarray(x), jnp.asarray(y)))

    def accuracy(self, w, x, y) -> float:
        return float(self._acc(jnp.asarray(w, jnp.float32),
                               jnp.asarray(x), jnp.asarray(y)))


class SyntheticHighDimTask:
    """Payload-scale synthetic task: f_m(w) = 1/2 ||w - c_m||^2 per device.

    Built for the large-d kernel harness (d up to 10^7): gradients are
    O(d) closed-form (``clip(w - c_m)``), so the bench can stream per-device
    gradient chunks without holding a dataset of comparable size. The
    device "data" is just its integer id — ``device_data`` returns
    (N, 1, 1) xs carrying the id and dummy (N, 1) ys — and each center
    c_m is a counter-based threefry normal keyed on (seed, m), generated
    on demand inside the jit. Exposes the same ``device_grads_fn`` /
    ``device_grads_at_fn`` protocol as the learning tasks so it can drive
    the engine or the bench interchangeably.
    """

    def __init__(self, dim: int, g_max: float = 1e9, seed: int = 0):
        self.dim = dim
        self.g_max = g_max
        self._seed = seed
        base = jax.random.PRNGKey(seed)

        def center(dev_id):
            return jax.random.normal(jax.random.fold_in(base, dev_id),
                                     (dim,), dtype=jnp.float32)

        def loss(w_flat, x, y):
            c = center(x[0, 0].astype(jnp.int32))
            return 0.5 * jnp.sum((w_flat - c) ** 2)

        def device_grad(w_flat, x, y):
            c = center(x[0, 0].astype(jnp.int32))
            return _clip_to(w_flat - c, g_max)

        self._loss = jit_f32(loss)
        self._device_grads = jit_f32(jax.vmap(device_grad,
                                              in_axes=(None, 0, 0)))
        self._device_grads_at = jit_f32(
            jax.vmap(_device_grad_at(device_grad), in_axes=(None, 0, 0, 0)))
        self._acc = jit_f32(lambda w_flat, x, y: jnp.float32(0.0))

    def init_params(self, seed: int = 0) -> np.ndarray:
        return np.zeros(self.dim, dtype=np.float64)

    def device_data(self, n_devices: int):
        """(xs, ys) stand-in dataset: xs[m] = [[m]] (the id), ys dummy."""
        xs = np.arange(n_devices, dtype=np.float32).reshape(n_devices, 1, 1)
        ys = np.zeros((n_devices, 1), dtype=np.int32)
        return xs, ys

    @property
    def loss_fn(self):
        return self._loss

    @property
    def accuracy_fn(self):
        return self._acc

    @property
    def device_grads_fn(self):
        return self._device_grads

    @property
    def device_grads_at_fn(self):
        return self._device_grads_at

    def device_grads(self, w, xs, ys):
        g = self._device_grads(jnp.asarray(w, jnp.float32),
                               jnp.asarray(xs), jnp.asarray(ys))
        return np.asarray(g, dtype=np.float64)

    def global_loss(self, w, x, y) -> float:
        return float(self._loss(jnp.asarray(w, jnp.float32),
                                jnp.asarray(x), jnp.asarray(y)))

    def accuracy(self, w, x, y) -> float:
        return 0.0
