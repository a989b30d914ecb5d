"""Fig. 2 OTA configuration: the program's objects for one Monte-Carlo cell.

``program(config, traffic, dataset(config))`` makes the paper's Fig. 2a/b set-up from the
configuration file: softmax regression on benchmark-made MNIST-shaped
data, N=50 devices on a 1750 m disk, and the proposed OTA design stored in
the file. Run as a script, it solves kappa and the design again with the
program's own estimator and solver and prints the ``design`` block:

    PYTHONPATH=src python bench/configs/fig2_mnist_ota.py
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import data  # noqa: E402


def eta_max(config: dict) -> float:
    """2/(mu + L) with L = 2 + mu: the paper's softmax step-size rule."""
    mu = config["task"]["mu"]
    return 2.0 / (mu + 2.0 + mu)


def grad_flops_per_device(config: dict) -> int:
    """Matmul FLOPs of one device's full-batch softmax-regression gradient:
    the logits ``x W^T`` and the weight gradient ``(P - Y)^T x``, each
    ``2 n f C``."""
    t, d = config["task"], config["data"]
    return 4 * d["samples_per_device"] * t["n_features"] * t["n_classes"]


def grad_flops_per_round(config: dict, traffic: dict) -> int:
    """Gradient FLOPs one round's update uses: every device's."""
    return config["wireless"]["n_devices"] * grad_flops_per_device(config)


def dataset(config: dict):
    """The benchmark-made device data ``(xs, ys, x_test, y_test)``."""
    return data.federated_dataset(config["data"],
                                  config["wireless"]["n_devices"])


def program(config: dict, traffic: dict, arrays):
    """``(engine, aggregator, run_kwargs)`` for the cell's traffic."""
    from repro.core import baselines as B
    from repro.core.channel import WirelessConfig, make_deployment
    from repro.core.ota import OTAParams
    from repro.data.loader import FLDataset
    from repro.fl.engine import FLEngine
    from repro.fl.tasks import SoftmaxRegressionTask

    t, w = config["task"], config["wireless"]
    n = w["n_devices"]
    xs, ys, x_te, y_te = arrays
    ds = FLDataset.from_shards([(xs[m], ys[m]) for m in range(n)],
                               x_te, y_te)
    task = SoftmaxRegressionTask(n_features=t["n_features"],
                                 n_classes=t["n_classes"], mu=t["mu"],
                                 g_max=t["g_max"])
    dep = make_deployment(WirelessConfig(**w))
    cfg = dep.cfg
    if traffic["scheme"] != "proposed_ota":
        raise ValueError(f"fig2_mnist_ota has no scheme {traffic['scheme']!r}")
    des = config["design"]
    agg = B.ProposedOTA(OTAParams(
        gammas=np.asarray(des["gammas"], np.float64),
        alpha=float(des["alpha"]), g_max=t["g_max"], dim=task.dim,
        energy_per_symbol=cfg.energy_per_symbol, noise_psd=cfg.noise_power))
    eng = FLEngine(task, ds, dep, traffic["eta_frac"] * eta_max(config))
    kw = {k: traffic[k] for k in ("rounds", "trials", "eval_every")}
    return eng, agg, kw


def regenerate(config: dict) -> dict:
    """kappa on the configuration's data and the proposed OTA design (15),
    by the program's estimator and batched solver."""
    from repro.api.materialize import estimate_kappa_sc
    from repro.core import ota_design
    from repro.core.bounds import ObjectiveWeights
    from repro.core.channel import WirelessConfig, make_deployment
    from repro.data.loader import FLDataset
    from repro.fl.tasks import SoftmaxRegressionTask

    t, w = config["task"], config["wireless"]
    n = w["n_devices"]
    xs, ys, x_te, y_te = data.federated_dataset(config["data"], n)
    ds = FLDataset.from_shards([(xs[m], ys[m]) for m in range(n)],
                               x_te, y_te)
    task = SoftmaxRegressionTask(n_features=t["n_features"],
                                 n_classes=t["n_classes"], mu=t["mu"],
                                 g_max=t["g_max"])
    kappa = estimate_kappa_sc(task, ds, iters=1500)
    dep = make_deployment(WirelessConfig(**w))
    cfg = dep.cfg
    weights = ObjectiveWeights.strongly_convex(
        eta=eta_max(config), mu=t["mu"], kappa_sc=kappa, n=n)
    spec = ota_design.OTADesignSpec(
        lambdas=dep.lambdas, dim=task.dim, g_max=t["g_max"],
        e_s=cfg.energy_per_symbol, n0=cfg.noise_power, weights=weights)
    params, objs = ota_design.design_ota_batch([spec])
    return {"kappa": kappa, "objective": float(objs[0]),
            "gammas": [float(g) for g in params[0].gammas],
            "alpha": float(params[0].alpha)}


if __name__ == "__main__":
    here = Path(__file__).with_suffix(".json")
    print(json.dumps(regenerate(json.loads(here.read_text())), indent=1))
