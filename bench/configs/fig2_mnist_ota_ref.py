"""Plain reference of one Fig. 2 OTA Monte-Carlo call.

Written from the paper's equations and the engine's documented random
streams, importing nothing of the program: per trial, full-batch
softmax-regression gradients clipped to G_max (Assumption 1), truncated
channel inversion chi_m = 1{|h_m| >= G_max gamma_m / sqrt(d E_s)} (eq. 5),
ghat = (sum_m chi_m gamma_m g_m + sqrt(N0) z) / alpha (eq. 6) and
w <- w - eta ghat; loss and test accuracy at every eval point. The
design it is given (gammas, alpha) is first checked against problem (15)
(:func:`check_design`).

The random streams and the deployment are those of ``bench/streams.py``.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from bench import data, streams
from bench.precision import einsum


#: Largest relative gaps :func:`check_design` accepts: stored values are
#: those of the same formulas in float64; a solved design is stationary.
DESIGN_RTOL = 1e-9
DESIGN_STATIONARY = 1e-6


def check_design(config: dict) -> dict:
    """The stored design against problem (15), from the paper's formulas.

    With truncation threshold tau_m = G gamma_m / sqrt(d E_s) a device sends
    only when |h_m| >= tau_m, so every symbol's energy is at most E_s and
    the expected receive scale is alpha_m = gamma_m exp(-c_m gamma_m^2),
    c_m = G^2 / (d Lambda_m E_s), largest at gamma_m,max = sqrt(d Lambda_m
    E_s / (2 G^2)). Checked: 0 < gamma_m <= gamma_m,max; alpha = sum_m alpha_m,
    so p_m = alpha_m / alpha lies on the simplex (15b, 15e); the objective
    (15a) omega_var (sum_m p_m^2 G^2 (gamma_m / alpha_m - 1) + d N0 /
    alpha^2) + omega_bias sum_m (p_m - 1/N)^2, omega_var = eta_max / mu,
    omega_bias = N kappa^2 / mu^2, equals the stored one; and no gamma_m can
    move to lower it to first order (central differences in log gamma_m).
    Raises ``ValueError`` on a violation; returns the readings.
    """
    t, w, des = config["task"], config["wireless"], config["design"]
    lam, e_s, n0 = streams.wireless_constants(w)
    n, mu, g2 = w["n_devices"], t["mu"], t["g_max"] ** 2
    d = t["n_classes"] * (t["n_features"] + 1)
    w_var = 2.0 / (mu + 2.0 + mu) / mu
    w_bias = n * des["kappa"] ** 2 / mu ** 2
    c = g2 / (d * lam * e_s)
    gam = np.asarray(des["gammas"], np.float64)
    gam_max = np.sqrt(d * lam * e_s / (2.0 * g2))
    over = float(np.max(gam / gam_max))
    if not (np.all(gam > 0) and over <= 1.0 + 1e-12):
        raise ValueError("the stored design does not solve (15): gamma "
                         f"outside (0, gamma_max], up to {over:.6g} of it")

    def objective(g):
        a = g * np.exp(-c * g ** 2)
        alpha = a.sum()
        p = a / alpha
        var = np.sum(p ** 2 * g2 * (np.exp(c * g ** 2) - 1.0)) \
            + d * n0 / alpha ** 2
        return w_var * var + w_bias * np.sum((p - 1.0 / n) ** 2), alpha

    obj, alpha = objective(gam)
    h = 1e-6
    grad = np.empty(n)
    for m in range(n):
        up, dn = gam.copy(), gam.copy()
        up[m] *= 1.0 + h
        dn[m] *= 1.0 - h
        grad[m] = (objective(up)[0] - objective(dn)[0]) / (2.0 * h)
    at_top = gam >= gam_max * (1.0 - 1e-9)
    grad = np.where(at_top, np.minimum(grad, 0.0), grad)
    out = {"objective_gap": abs(obj - des["objective"]) / obj,
           "alpha_gap": abs(alpha - des["alpha"]) / alpha,
           "gamma_over_max": over,
           "stationarity": float(np.max(np.abs(grad)) / obj)}
    if not (out["objective_gap"] <= DESIGN_RTOL
            and out["alpha_gap"] <= DESIGN_RTOL
            and out["stationarity"] <= DESIGN_STATIONARY):
        raise ValueError(f"the stored design does not solve (15): {out}")
    return out


class Reference:
    """The reference for one configuration and traffic, data made once."""

    def __init__(self, config: dict, traffic: dict,
                 precision: str = "highest"):
        if traffic["scheme"] != "proposed_ota":
            raise ValueError("the reference covers proposed OTA")
        check_design(config)
        t, w = config["task"], config["wireless"]
        self.n_dev = w["n_devices"]
        xs, ys, x_te, y_te = data.federated_dataset(config["data"],
                                                    self.n_dev)
        self.C, self.f = t["n_classes"], t["n_features"]
        self.d = self.C * (self.f + 1)
        self.lam, e_s, n0 = streams.wireless_constants(w)
        des = config["design"]
        gam = np.asarray(des["gammas"], np.float64)
        self.tau = t["g_max"] * gam / np.sqrt(self.d * e_s)
        self.traffic = traffic
        self.bandwidth = w["bandwidth_hz"]
        mu, g_max = t["mu"], t["g_max"]
        eta = traffic["eta_frac"] * 2.0 / (mu + 2.0 + mu)
        C, f, p = self.C, self.f, precision
        f32 = jnp.float32
        # the data and tables are arguments of the jitted functions, not
        # constants baked into them
        self._data = (jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(x_te),
                      jnp.asarray(y_te))
        self._tables = (jnp.asarray(self.tau, f32), jnp.asarray(gam, f32))
        inv_alpha = f32(1.0 / des["alpha"])
        root_n0 = f32(np.sqrt(n0))

        def logits(W, x):
            return einsum("...nf,cf->...nc", x, W[:, :f], p) + W[:, f]

        def grads(wv, xs, ys):
            W = wv.reshape(C, f + 1)
            prob = jax.nn.softmax(logits(W, xs), axis=-1)      # (N, n, C)
            err = (prob - jax.nn.one_hot(ys, C, dtype=f32)) / xs.shape[1]
            gw = einsum("mnc,mnf->mcf", err, xs, p)
            g = jnp.concatenate([gw, err.sum(1)[..., None]], axis=-1)
            g = g.reshape(self.n_dev, -1) + mu * wv
            nrm = jnp.sqrt(jnp.sum(g * g, axis=1, keepdims=True))
            return g * jnp.minimum(1.0, g_max / jnp.maximum(nrm, 1e-12))

        def step(wv, h, z, xs, ys, tau, gam32):
            g = grads(wv, xs, ys)
            chi = (jnp.abs(h) >= tau).astype(f32)
            acc = einsum("m,md->d", chi * gam32, g, p)
            ghat = acc * inv_alpha + (root_n0 * z) * inv_alpha
            return wv - eta * ghat

        def evaluate(wv, xs, ys, x_te, y_te):
            W = wv.reshape(C, f + 1)
            logp = jax.nn.log_softmax(logits(W, xs.reshape(-1, f)), axis=-1)
            nll = -jnp.mean(jnp.take_along_axis(logp, ys.reshape(-1, 1), 1))
            loss = nll + 0.5 * mu * jnp.sum(wv * wv)
            acc = jnp.mean(jnp.argmax(logits(W, x_te), -1) == y_te)
            return loss, acc

        self._step, self._evaluate = jax.jit(step), jax.jit(evaluate)

    def run(self, seed: int) -> dict:
        """States, losses, accuracies and wall-clock at every eval point
        of the call made with ``seed``."""
        tr = self.traffic
        T, every = tr["rounds"], tr["eval_every"]
        T -= T % every
        ws, losses, accs = [], [], []
        for r in range(tr["trials"]):
            H = jnp.asarray(streams.fading(self.lam, seed, r, T))
            Z = jnp.asarray(streams.noise(seed, r, T, self.d))
            wv = jnp.zeros(self.d, jnp.float32)
            states = [wv]
            for t in range(T):
                wv = self._step(wv, H[t], Z[t], *self._data[:2],
                                *self._tables)
                if (t + 1) % every == 0:
                    states.append(wv)
            ev = [self._evaluate(s, *self._data) for s in states]
            ws.append(np.stack([np.asarray(s) for s in states]))
            losses.append([float(e[0]) for e in ev])
            accs.append([float(e[1]) for e in ev])
        rounds = np.arange(0, T + 1, every)
        return {"ws": np.stack(ws), "loss": np.asarray(losses),
                "acc": np.asarray(accs),
                "wall": rounds * self.d / self.bandwidth}
