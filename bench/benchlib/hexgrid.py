"""A hexagonal macro-cell deployment and its mobility chain, made from a
configuration file and a seed.

Layout ``"hex"`` (3GPP TR 38.901 Table 7.2-1, UMa): ``n_servers`` sites on
a hexagonal grid of ``columns`` sites a row at ``isd_m`` inter-site
distance, odd rows shifted by half of it; devices uniform over the grid's
rectangular hull, which holds one hexagonal cell of area
(sqrt(3)/2) isd^2 a site; reach ``reach_m``. A share ``active_share`` of
the devices is active at the start, the rest parked for arrivals. Every
Table II number comes from the configuration, through
``benchlib.scenarios._assemble`` (one channel gain per device, to its
nearest site, held through mobility).

The chain (``Chain``) moves the deployment one tick at a time, each tick
drawn from the chain's seed and the tick's number, so that every run sees
the same ticks: indoor devices (a share ``indoor_share``, fixed per device)
move ``indoor_mps`` and the others ``outdoor_mps`` times ``tick_s`` in a
uniform random direction, clipped to the hull; a share ``depart_share`` of
the active devices departs and as many parked ones arrive. Distances and
reach are recomputed for the moved columns, and every device keeps at
least its nearest site (constraint 17e).
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from benchlib.sample import sub_seed
from benchlib.scenarios import _assemble, _pairwise_dist


def sites(n: int, columns: int, isd: float) -> tuple[np.ndarray, tuple]:
    """(n, 2) site positions and the hull (x0, x1, y0, y1)."""
    cols = min(columns, n)
    rows = -(-n // cols)
    h = isd * math.sqrt(3.0) / 2.0
    i = np.arange(n)
    r, c = i // cols, i % cols
    xy = np.stack([(c + 0.5 * (r % 2)) * isd, r * h], axis=1)
    hull = (-0.25 * isd, cols * isd - 0.25 * isd, -0.5 * h, (rows - 0.5) * h)
    return xy, hull


def make_hex_deployment(cfg: dict, seed: int):
    """The configuration's deployment, drawn from ``seed``; returns the
    scenario, the hull and each device's indoor flag."""
    rng = np.random.default_rng(seed)
    n, k = int(cfg["n_devices"]), int(cfg["n_servers"])
    lay = cfg["layout"]
    if lay["kind"] != "hex":
        raise ValueError(f"not a hex layout: {lay['kind']!r}")
    srv_xy, hull = sites(k, int(lay["columns"]), float(lay["isd_m"]))
    x0, x1, y0, y1 = hull
    dev_xy = np.stack([rng.uniform(x0, x1, n), rng.uniform(y0, y1, n)], 1)
    sc = _assemble(rng, cfg["table2"], cfg["learning"], dev_xy, srv_xy,
                   float(lay["reach_m"]))
    active = np.zeros(n, bool)
    active[rng.permutation(n)[:round(lay["active_share"] * n)]] = True
    indoor = rng.uniform(size=n) < lay["indoor_share"]
    return dataclasses.replace(sc, active=active), hull, indoor


class Chain:
    """The deployment's ticks, the same for every run."""

    def __init__(self, cfg: dict, traffic: dict):
        self.traffic = traffic
        self.base, self.hull, indoor = make_hex_deployment(
            cfg, traffic["chain_seed"])
        t = traffic["tick_s"]
        self.step_m = np.where(indoor, traffic["indoor_mps"] * t,
                               traffic["outdoor_mps"] * t)

    def tick(self, sc, j: int):
        """Tick ``j`` applied to ``sc``: a new scenario, ``sc`` unchanged."""
        rng = np.random.default_rng(sub_seed(self.traffic["chain_seed"], j))
        active = sc.active_mask.copy()
        act, parked = np.flatnonzero(active), np.flatnonzero(~active)
        m = min(round(self.traffic["depart_share"] * act.size), parked.size)
        active[rng.choice(act, m, replace=False)] = False
        active[rng.choice(parked, m, replace=False)] = True
        moved = np.flatnonzero(active)
        angle = rng.uniform(0.0, 2.0 * math.pi, moved.size)
        x0, x1, y0, y1 = self.hull
        xy = np.array(sc.dev_xy, copy=True)
        step = self.step_m[moved]
        xy[moved, 0] = np.clip(xy[moved, 0] + step * np.cos(angle), x0, x1)
        xy[moved, 1] = np.clip(xy[moved, 1] + step * np.sin(angle), y0, y1)
        dist = np.array(sc.dist, copy=True)
        avail = np.array(sc.avail, copy=True)
        dist[:, moved] = _pairwise_dist(sc.srv_xy, xy[moved])
        avail[:, moved] = dist[:, moved] <= sc.reach_m
        lost = moved[~avail[:, moved].any(axis=0)]
        avail[np.argmin(dist[:, lost], axis=0), lost] = True
        return dataclasses.replace(sc, avail=avail, dist=dist, active=active,
                                   dev_xy=xy)
