"""CPU tests of the plain reference's group solver: it finds the optimum
of problem (18), whatever the group, in float64."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from benchlib import reference as ref  # noqa: E402
from benchlib.scenarios import make_deployment  # noqa: E402

CFG = json.loads((BENCH / "configs" / "paper_t2_optimal.json").read_text())


def model(seed=3):
    return ref.Model(make_deployment(CFG, seed))


def random_groups(m, count, seed, widths=(1, 24)):
    rng = np.random.default_rng(seed)
    return [(int(rng.integers(0, m.k)),
             np.sort(rng.choice(m.n, int(rng.integers(*widths)),
                                replace=False)))
            for _ in range(count)]


def plain_cost(m, groups):
    cost = ref.solve_groups(m, groups)[0]
    return cost - m.cloud[[s for s, _ in groups]]


def test_no_feasible_perturbation_does_better():
    m = model()
    groups = random_groups(m, 12, 1)
    cost, f, beta, idx, mask = ref.solve_groups(m, groups)
    cost = cost - m.cloud[[s for s, _ in groups]]
    servers = np.array([s for s, _ in groups])
    c = m.consts(servers, idx)
    rng = np.random.default_rng(2)
    for _ in range(200):
        nb = np.where(mask, beta * np.exp(rng.normal(0, 0.05, beta.shape)),
                      0.0)
        nb = nb / nb.sum(axis=1, keepdims=True)
        nf = np.clip(f * np.exp(rng.normal(0, 0.05, f.shape)),
                     c["f_min"], c["f_max"])
        other = ref._objective(c, mask, np.where(mask, nf, 1.0), nb,
                               np.float64)
        assert np.all(other >= cost * (1 - 1e-12))


def test_two_devices_against_a_dense_grid():
    """Each share of the band and each deadline on a fine grid, with f the
    least that meets the deadline: the grid's best is no better than the
    reference's optimum and lies within the grid's resolution of it."""
    m = model()
    for g, members in enumerate(([3, 17], [0, 41], [8, 9])):
        group = [(g % m.k, np.array(members))]
        best = plain_cost(m, group)[0]
        c = m.consts(np.array([g % m.k]), np.array([members]))
        x = np.linspace(1e-3, 1 - 1e-3, 1500)[:, None]
        beta = np.stack([x, 1 - x], axis=-1)                    # (X, 1, 2)
        lo = np.max(c["d"][0] / beta + c["e"][0] / c["f_max"][0], axis=-1)
        t = lo * np.geomspace(1.0 + 1e-9, 30.0, 1500)[None, :]  # (X, T)
        slack = t[..., None] - c["d"][0] / beta
        f = np.clip(c["e"][0] / slack, c["f_min"][0], c["f_max"][0])
        obj = (np.sum(c["a"][0] / beta + c["b"][0] * f * f, axis=-1)
               + c["w"][0] * t)
        assert obj.min() >= best * (1 - 1e-12)
        assert obj.min() <= best * (1 + 2e-3)


def test_interior_devices_share_one_deadline_and_the_kkt_shares():
    """KKT of (18): a device whose f is inside its box finishes at the
    common deadline, with beta^2 proportional to a + tau d, tau = 2 b f^3/e."""
    m = model(5)
    groups = random_groups(m, 6, 4, widths=(6, 20))
    _, f, beta, idx, mask = ref.solve_groups(m, groups)
    c = m.consts(np.array([s for s, _ in groups]), idx)
    for g in range(len(groups)):
        inner = (mask[g] & (f[g] > c["f_min"][g] * (1 + 1e-6))
                 & (f[g] < c["f_max"][g] * (1 - 1e-6)))
        if inner.sum() < 2:
            continue
        finish = (c["d"][g] / beta[g] + c["e"][g] / f[g])[inner]
        assert finish.max() / finish.min() - 1 < 1e-9
        tau = 2 * c["b"][g] * f[g] ** 3 / c["e"][g]
        nu = (c["a"][g] + tau * c["d"][g])[inner] / beta[g][inner] ** 2
        assert nu.max() / nu.min() - 1 < 1e-6


def test_more_iterations_change_nothing():
    m = model(7)
    groups = random_groups(m, 20, 6)
    twice = {k: 2 * v for k, v in ref.SCHEDULE.items()}
    a = plain_cost(m, groups)
    b = ref.solve_groups(m, groups, schedule=twice)[0] - m.cloud[
        [s for s, _ in groups]]
    assert np.max(np.abs(a - b) / a) < 1e-10


def test_a_group_costs_the_same_alone_and_packed():
    m = model(9)
    groups = random_groups(m, 8, 8)
    packed = plain_cost(m, groups)
    for j, grp in enumerate(groups):
        alone = plain_cost(m, [grp])[0]
        assert alone == pytest.approx(packed[j], rel=1e-12)


def test_a_lone_device_takes_the_whole_band_and_empty_costs_nothing():
    m = model(11)
    cost, f, beta, idx, mask = ref.solve_groups(
        m, [(1, np.array([4])), (2, np.array([], np.int64))])
    assert beta[0, 0] == pytest.approx(1.0, rel=1e-9)
    assert cost[1] == 0.0


def test_the_bfloat16_solver_lands_off_the_optimum():
    """The control's solver is the same algorithm in bfloat16: near the
    optimum, and measurably off it."""
    m = model(13)
    groups = random_groups(m, 20, 12)
    exact = plain_cost(m, groups)
    low = (ref.solve_groups(m, groups, ref.BF16)[0].astype(np.float64)
           - m.cloud[[s for s, _ in groups]])
    rel = np.abs(low - exact) / exact
    assert 1e-4 < rel.max() < 0.05
