"""Uplink at the width of FedAvg's MNIST CNN: N=100 devices uploading a
1,663,370-wide update every round (McMahan et al. 2017, Sec. 3, Table 1).

The task is the program's ``SyntheticHighDimTask`` (f_m(w) = ||w - c_m||^2/2
with threefry centers) at the CNN's width, so the local gradients are
cheap and the uplink (random streams, quantize -> pack -> packed sum, or
the OTA combine) is the work.
"""
from __future__ import annotations


def eta_max(config: dict) -> float:
    """2/(mu + L), the paper's step-size rule (1 for this task)."""
    t = config["task"]
    return 2.0 / (t["mu"] + t["smooth_l"])


def grad_flops_per_round(config: dict, traffic: dict) -> int:
    """``w - c_m``, its squared norm (2 d) and the clip scale: 4 d per
    device, every device every round."""
    return 4 * config["task"]["dim"] * config["wireless"]["n_devices"]


def dataset(config: dict):
    """The devices' stand-in data: each device's id (the task makes its
    center from it)."""
    import numpy as np
    n = config["wireless"]["n_devices"]
    xs = np.arange(n, dtype=np.float32).reshape(n, 1, 1)
    return xs, np.zeros((n, 1), np.int32)


def program(config: dict, traffic: dict, arrays):
    """``(engine, aggregator, run_kwargs)`` for the cell's traffic."""
    from repro.core import baselines as B
    from repro.core.channel import WirelessConfig, make_deployment
    from repro.data.loader import FLDataset
    from repro.fl.engine import FLEngine
    from repro.fl.tasks import SyntheticHighDimTask

    t = config["task"]
    xs, ys = arrays
    n = xs.shape[0]
    task = SyntheticHighDimTask(t["dim"], g_max=t["g_max"],
                                seed=t["center_seed"])
    ds = FLDataset.from_shards([(xs[m], ys[m]) for m in range(n)],
                               xs[0], ys[0])
    dep = make_deployment(WirelessConfig(**config["wireless"]))
    cfg = dep.cfg
    scheme = traffic["scheme"]
    if scheme == "best_channel":
        agg = B.BestChannel(dep, t["dim"], t["g_max"], cfg.energy_per_symbol,
                            cfg.noise_power, cfg.bandwidth_hz,
                            k=traffic["k"] or n, r_bits=traffic["r_bits"])
    elif scheme == "vanilla_ota":
        agg = B.VanillaOTA(t["dim"], t["g_max"], cfg.energy_per_symbol,
                           cfg.noise_power)
    else:
        raise ValueError(f"fedavg_cnn_uplink has no scheme {scheme!r}")
    eng = FLEngine(task, ds, dep, traffic["eta_frac"] * eta_max(config))
    kw = {k: traffic[k] for k in ("rounds", "trials", "eval_every")}
    return eng, agg, kw
