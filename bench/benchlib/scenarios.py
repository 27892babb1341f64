"""Deployments, made from a configuration file and a seed.

A copy of the program's scenario generator (``make_scenario`` in
``src/repro/core/scenario.py``), kept here so that no later change to the
program can change the benchmark's inputs. Every Table II number comes from
the configuration file. With the paper's numbers the copy draws the same
values as the program's generator (a test pins this), and it returns the
program's own ``Scenario`` type, which is what the system under test takes.

Layout ``"uniform"``: devices and servers uniform in a square of ``area_m``
(the paper's Sec. VI set-up), reach ``reach_m``. A later layout is a new
``kind`` here and a new configuration file.
"""

from __future__ import annotations

import numpy as np

from repro.core.cost_model import DeviceParams, LearningParams, ServerParams
from repro.core.scenario import Scenario


def _pairwise_dist(srv_xy: np.ndarray, dev_xy: np.ndarray,
                   chunk: int = 16_384) -> np.ndarray:
    k, n = srv_xy.shape[0], dev_xy.shape[0]
    out = np.empty((k, n), dtype=np.float64)
    for lo in range(0, max(n, 1), chunk):
        sl = slice(lo, min(lo + chunk, n))
        out[:, sl] = np.linalg.norm(srv_xy[:, None, :] - dev_xy[None, sl, :],
                                    axis=-1)
    return out


def _gain(dist_m: np.ndarray) -> np.ndarray:
    """h = 10^(-PL/10), PL = 128.1 + 37.6 log10(d_km)."""
    d_km = np.maximum(dist_m, 1.0) / 1000.0
    return 10.0 ** (-(128.1 + 37.6 * np.log10(d_km)) / 10.0)


def make_deployment(cfg: dict, seed: int) -> Scenario:
    """The configuration's deployment, drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    n, k = int(cfg["n_devices"]), int(cfg["n_servers"])
    lay = cfg["layout"]
    if lay["kind"] == "uniform":
        area = float(lay["area_m"])
        reach = float(lay["reach_m"])
        dev_xy = rng.uniform(0.0, area, size=(n, 2))
        srv_xy = rng.uniform(0.0, area, size=(k, 2))
    else:
        raise ValueError(f"unknown layout {lay['kind']!r}")
    return _assemble(rng, cfg["table2"], cfg["learning"], dev_xy, srv_xy,
                     reach)


def _assemble(rng, t2: dict, learning: dict, dev_xy, srv_xy,
              reach_m: float) -> Scenario:
    f32 = np.float32
    n, k = dev_xy.shape[0], srv_xy.shape[0]
    dist = _pairwise_dist(srv_xy, dev_xy)
    lo, hi = t2["data_mb"]
    data_bits = rng.uniform(lo * 1e6, hi * 1e6, n) * 8.0
    lo, hi = t2["cycles_per_bit"]
    density = rng.uniform(lo, hi, n)
    # power-law sample counts: aggregation weights only
    samples = np.floor(rng.pareto(t2["samples_pareto_a"], n)
                       * t2["samples_scale"] + t2["samples_min"])
    # one channel gain per device, to its nearest server, with shadowing
    nearest = np.argmin(dist, axis=0)
    h = _gain(dist[nearest, np.arange(n)])
    h *= rng.lognormal(0.0, t2["shadowing_sigma"], n)
    dev = DeviceParams(
        cycles_per_iter=(density * data_bits).astype(f32),
        data_samples=samples.astype(f32),
        model_nats=np.full(n, t2["model_nats"], f32),
        tx_power=np.full(n, t2["tx_power_w"], f32),
        channel_gain=h.astype(f32),
        alpha=np.full(n, t2["capacitance"], f32),
        f_min=np.full(n, t2["f_min_hz"], f32),
        f_max=np.full(n, t2["f_max_hz"], f32),
    )
    lo, hi = t2["cloud_rate_nats_s"]
    srv = ServerParams(
        bandwidth=np.full(k, t2["bandwidth_hz"], f32),
        noise=np.full(k, t2["noise_w"], f32),
        cloud_rate=rng.uniform(lo, hi, k).astype(f32),
        cloud_power=np.full(k, t2["cloud_power_w"], f32),
        cloud_nats=np.full(k, t2["cloud_nats"], f32),
    )
    avail = dist <= reach_m
    # constraint (17e): every device reaches at least its nearest server
    lost = ~avail.any(axis=0)
    avail[nearest[lost], lost] = True
    return Scenario(dev=dev, srv=srv, avail=avail, dist=dist,
                    lp=LearningParams(**learning), dev_xy=dev_xy.copy(),
                    srv_xy=srv_xy.copy(), reach_m=float(reach_m))
