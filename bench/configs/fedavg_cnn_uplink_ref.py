"""Plain reference of one Monte-Carlo call of the CNN-width uplink.

Written from the schemes' definitions and the engine's documented random
streams (``bench/streams.py``), importing nothing of the program. Device m's
loss is ||w - c_m||^2 / 2 with c_m = normal(fold_in(PRNGKey(center_seed),
m), (d,)) in float32; its gradient w - c_m is clipped to G_max. Per round:

* best channel: the k devices of largest |h| upload r-bit dithered codes of
  their gradient (step 2 m_i / (2^r - 1) with m_i = ||g_i||_inf; code
  ``clip(floor(x) + [u < x - floor(x)], 0, 2^r - 1)`` with
  x = (g + m_i) / step); the server averages the dequantized codes
  ``-m_i + step q`` over the k; the round lasts
  sum_selected (64 + d r) / (B log2(1 + E_s |h|^2 / N0)) seconds;
* vanilla OTA: every device inverts its channel with the common pre-scaler
  gamma = sqrt(d E_s) min|h| / G_max; ghat = (gamma sum g + sqrt(N0) z) /
  (N gamma); the round lasts d / B seconds.

Then w <- w - eta ghat, eta = eta_frac 2/(mu + L). With ``precision="bf16"`` (the control) every
uploaded gradient is rounded to bfloat16 first (``lax.reduce_precision``,
which no compiler pass removes). The engine's global loss of this task is the loss
of the first device's center, ||w - c_0||^2 / 2, and its accuracy 0; the
reference reports the same. Devices are handled in blocks, so the (N, d)
gradients never exist at once.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from bench import streams

BLOCK = 32


class Reference:
    """The reference for one configuration and traffic."""

    def __init__(self, config: dict, traffic: dict,
                 precision: str = "highest"):
        if precision not in ("highest", "bf16"):
            raise ValueError(f"no {precision!r} payload reference")
        t, w = config["task"], config["wireless"]
        self.d, self.n = t["dim"], w["n_devices"]
        self.traffic = traffic
        self.scheme = traffic["scheme"]
        if self.scheme not in ("best_channel", "vanilla_ota"):
            raise ValueError(f"no reference for scheme {self.scheme!r}")
        self.lam, self.e_s, self.n0 = streams.wireless_constants(w)
        self.bw = w["bandwidth_hz"]
        self.g_max = t["g_max"]
        self.eta = traffic["eta_frac"] * 2.0 / (t["mu"] + t["smooth_l"])
        base = jax.random.PRNGKey(t["center_seed"])
        g_max = np.float32(t["g_max"])
        d = self.d

        def grads(wv, ids):
            c = jax.vmap(lambda m: jax.random.normal(
                jax.random.fold_in(base, m), (d,), jnp.float32))(ids)
            g = wv[None] - c
            nrm = jnp.sqrt(jnp.sum(g * g, axis=1, keepdims=True))
            g = g * jnp.minimum(1.0, g_max / jnp.maximum(nrm, 1e-12))
            if precision == "bf16":
                g = jax.lax.reduce_precision(g, exponent_bits=8,
                                             mantissa_bits=7)
            return g

        def quantized_sum(wv, ids, u, levels, weights):
            g = grads(wv, ids)
            m = jnp.max(jnp.abs(g), axis=1, keepdims=True)
            lv = levels[:, None]
            ok = (lv > 0) & (m > 0)
            step = jnp.where(ok, 2.0 * m / jnp.where(lv > 0, lv, 1.0), 1.0)
            x = (g + m) / step
            lo = jnp.floor(x)
            q = jnp.clip(lo + (u < x - lo).astype(jnp.float32), 0.0, lv)
            deq = jnp.where(ok, -m + step * q, 0.0)
            return jnp.sum(weights[:, None] * deq, axis=0)

        def grad_sum(wv, ids):
            return jnp.sum(grads(wv, ids), axis=0)

        self._qsum = jax.jit(quantized_sum)
        self._gsum = jax.jit(grad_sum)
        self._loss = jax.jit(lambda wv: 0.5 * jnp.sum(
            (wv - jax.random.normal(jax.random.fold_in(base, 0), (d,),
                                    jnp.float32)) ** 2))

    def _round(self, wv, habs, t, key, z):
        n, d, tr = self.n, self.d, self.traffic
        ghat = jnp.zeros(d, jnp.float32)
        if self.scheme == "best_channel":
            k, r = tr["k"] or n, tr["r_bits"]
            chi = np.zeros(n, np.float32)
            chi[np.argsort(-habs, kind="stable")[:k]] = 1.0
            levels = chi * np.float32(2 ** r - 1)
            u = streams.dither(key, t, n, d)
            for b in range(0, n, BLOCK):
                ids = jnp.arange(b, min(b + BLOCK, n))
                ghat = ghat + self._qsum(wv, ids, u[b:b + BLOCK],
                                         jnp.asarray(levels[b:b + BLOCK]),
                                         jnp.asarray(chi[b:b + BLOCK] / k))
            rate = np.log2(1.0 + self.e_s * habs.astype(np.float64) ** 2
                           / self.n0)
            lat = np.sum(chi * (64 + d * r) / (self.bw * np.maximum(rate,
                                                                    1e-9)))
        else:
            gamma = np.float32(np.sqrt(d * self.e_s) * habs.min()
                               / self.g_max)
            for b in range(0, n, BLOCK):
                ids = jnp.arange(b, min(b + BLOCK, n))
                ghat = ghat + self._gsum(wv, ids)
            inv = np.float32(1.0) / np.float32(n * gamma)
            ghat = (gamma * ghat) * inv + (np.float32(np.sqrt(self.n0)) * z) \
                * inv
            lat = d / self.bw
        return wv - np.float32(self.eta) * ghat, lat

    def run(self, seed: int) -> dict:
        tr = self.traffic
        T, every = tr["rounds"], tr["eval_every"]
        T -= T % every
        ws, losses, walls = [], [], []
        for r in range(tr["trials"]):
            habs = np.abs(streams.fading(self.lam, seed, r, T))
            key = streams.dither_key(seed, r)
            Z = (jnp.asarray(streams.noise(seed, r, T, self.d))
                 if self.scheme == "vanilla_ota" else None)
            wv = jnp.zeros(self.d, jnp.float32)
            states, wall, clock = [wv], [0.0], 0.0
            for t in range(T):
                wv, lat = self._round(wv, habs[t], t, key,
                                      None if Z is None else Z[t])
                clock += lat
                if (t + 1) % every == 0:
                    states.append(wv)
                    wall.append(clock)
            ws.append(np.stack([np.asarray(s) for s in states]))
            losses.append([float(self._loss(s)) for s in states])
            walls.append(wall)
        return {"ws": np.stack(ws), "loss": np.asarray(losses),
                "acc": np.zeros_like(np.asarray(losses)),
                "wall": np.mean(np.asarray(walls), axis=0)}
