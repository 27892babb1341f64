"""Section III solvers: feasibility, KKT structure, optimality cross-checks.

Property tests draw random problem instances (Table II ranges) and assert
the invariants every solver must satisfy plus mutual consistency between
the paper-faithful solver, the exact solver and the subgradient oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import make_scenario
from repro.core.cost_model import LearningParams, ra_constants, ra_objective
from repro.core import resource_allocation as ra


def _instance(seed: int, n_active: int, n_total: int = 16,
              lambda_t: float = 0.5):
    lp = LearningParams(lambda_e=1.0 - lambda_t, lambda_t=lambda_t)
    sc = make_scenario(n_total, 3, seed=seed, lp=lp)
    c = ra_constants(sc.dev, sc.srv.bandwidth[0], sc.srv.noise[0], sc.lp)
    mask = jnp.arange(n_total) < n_active
    return c, mask


def _check_feasible(c, mask, sol):
    beta = np.asarray(sol.beta)
    f = np.asarray(sol.f)
    m = np.asarray(mask)
    assert np.all(beta[m] > 0), "active betas must be positive"
    assert np.all(beta[~m] == 0), "padded betas must be zero"
    assert beta.sum() <= 1.0 + 1e-4, f"sum beta = {beta.sum()}"
    assert np.all(f[m] >= np.asarray(c.f_min)[m] * (1 - 1e-5))
    assert np.all(f[m] <= np.asarray(c.f_max)[m] * (1 + 1e-5))


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 10_000), n_active=st.integers(1, 16),
       lambda_t=st.floats(0.05, 0.95))
def test_solvers_feasible_and_ordered(seed, n_active, lambda_t):
    c, mask = _instance(seed, n_active, lambda_t=lambda_t)
    exact = ra.solve_exact(c, mask)
    fp = ra.solve_fixed_point(c, mask)
    paper = ra.solve_paper(c, mask)
    for sol in (exact, fp, paper):
        _check_feasible(c, mask, sol)
        assert np.isfinite(float(sol.cost))
    # the exact solver must not be beaten by the approximate ones
    assert float(exact.cost) <= float(fp.cost) * 1.01
    assert float(exact.cost) <= float(paper.cost) * 1.01


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10_000), n_active=st.integers(2, 12))
def test_exact_matches_subgradient_oracle(seed, n_active):
    c, mask = _instance(seed, n_active)
    exact = ra.solve_exact(c, mask)
    oracle = ra.solve_reference(c, mask)
    # within 2% of the structure-free oracle (subgradient is itself approx)
    assert float(exact.cost) <= float(oracle.cost) * 1.02


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), n_active=st.integers(2, 12))
def test_perturbation_optimality(seed, n_active):
    """No random feasible perturbation of the exact solution improves it."""
    c, mask = _instance(seed, n_active)
    sol = ra.solve_exact(c, mask)
    rng = np.random.default_rng(seed)
    base = float(sol.cost)
    beta = np.asarray(sol.beta)
    f = np.asarray(sol.f)
    m = np.asarray(mask)
    for _ in range(8):
        db = rng.normal(0, 0.02, beta.shape) * m
        nb = np.clip(beta + db, 1e-6, 1.0) * m
        nb = nb / max(nb.sum(), 1.0)  # keep sum <= 1
        nf = np.clip(f * (1 + rng.normal(0, 0.05, f.shape)),
                     np.asarray(c.f_min), np.asarray(c.f_max))
        safe_beta = jnp.where(mask, jnp.maximum(jnp.asarray(nb), 1e-12), 1.0)
        cost = float(ra_objective(c, mask, jnp.asarray(nf), safe_beta))
        assert cost >= base * (1 - 5e-3), (cost, base)


def test_beta_rule_eq19_normalization():
    c, mask = _instance(0, 8)
    f = jnp.sqrt(c.f_min * c.f_max)
    beta = ra.beta_of_f(c, mask, f)
    assert abs(float(beta.sum()) - 1.0) < 1e-5
    # proportionality: beta ratios match cube-root score ratios
    tau = 2 * c.b * f**3 / c.e
    score = jnp.cbrt(c.a + tau * c.d)
    ratio = np.asarray(beta)[:8] / np.asarray(score)[:8]
    assert np.allclose(ratio, ratio[0], rtol=1e-4)


def test_common_deadline_structure():
    """KKT: devices with interior f finish at the same time t* (eq. 25)."""
    c, mask = _instance(3, 10)
    sol = ra.solve_exact(c, mask)
    m = np.asarray(mask)
    f = np.asarray(sol.f)
    beta = np.maximum(np.asarray(sol.beta), 1e-12)
    finish = np.asarray(c.d) / beta + np.asarray(c.e) / f
    interior = m & (f > np.asarray(c.f_min) * 1.001) \
        & (f < np.asarray(c.f_max) * 0.999)
    if interior.sum() >= 2:
        times = finish[interior]
        assert times.max() / times.min() < 1.05, times


def test_partial_optimizers_are_worse_or_equal():
    """comp-only / comm-only optimization can't beat the joint optimum."""
    c, mask = _instance(1, 8)
    joint = float(ra.solve_exact(c, mask).cost)
    n_active = int(mask.sum())
    uniform = jnp.where(mask, 1.0 / n_active, 0.0)
    comp = float(ra.optimize_f_given_beta(c, mask, uniform).cost)
    f_rand = jnp.asarray(np.random.default_rng(0).uniform(
        np.asarray(c.f_min), np.asarray(c.f_max)).astype(np.float32))
    comm = float(ra.optimize_beta_given_f(c, mask, f_rand).cost)
    assert joint <= comp * 1.01
    assert joint <= comm * 1.01


def test_empty_group_zero_cost():
    c, mask = _instance(0, 0)
    for solver in (ra.solve_exact, ra.solve_fixed_point, ra.solve_paper):
        assert float(solver(c, jnp.zeros(16, bool)).cost) == 0.0


# ---------------------------------------------------------------------------
# The fast kind at the optimum of (18), against an independent reference
# ---------------------------------------------------------------------------

def _bench_reference():
    import sys
    from pathlib import Path

    bench = str(Path(__file__).resolve().parents[1] / "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from benchlib import reference
    return reference


# (lambda_t, capacitance scale): the paper's weights with f mostly at f_min,
# delay-heavy weights, and a capacitance 1000x smaller, which makes
# computing cheap and sends f to f_max
REGIMES = [(0.5, 1.0), (0.95, 1.0), (0.5, 1e-3)]


def _regime_groups(lambda_t, alpha_scale, seed):
    """Seeded groups of 2, 3, 4, 12 and 30 members of a 60-device, 5-server
    scenario, as (scenario, [(server, members)])."""
    import dataclasses

    lp = LearningParams(lambda_e=1.0 - lambda_t, lambda_t=lambda_t)
    sc = make_scenario(60, 5, seed=seed, lp=lp)
    sc = dataclasses.replace(sc, dev=dataclasses.replace(
        sc.dev, alpha=(np.asarray(sc.dev.alpha) * alpha_scale
                       ).astype(np.float32)))
    rng = np.random.default_rng(seed + 100)
    groups = [(int(rng.integers(5)),
               np.sort(rng.choice(60, size, replace=False)))
              for size in (2, 2, 3, 3, 4, 4, 12, 30)]
    return sc, groups


def _batched(sc, groups):
    from repro.core.cost_model import RAConstants

    cons = [ra_constants(sc.dev, sc.srv.bandwidth[s], sc.srv.noise[s], sc.lp)
            for s, _ in groups]
    cb = RAConstants(*(jnp.stack([getattr(c, f) for c in cons])
                       for f in RAConstants.__dataclass_fields__))
    masks = np.zeros((len(groups), sc.n_devices), bool)
    for i, (_, members) in enumerate(groups):
        masks[i, members] = True
    return cb, jnp.asarray(masks)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("lambda_t,alpha_scale", REGIMES,
                         ids=["paper", "delay", "cheap-compute"])
def test_fast_kind_prices_groups_at_the_optimum(lambda_t, alpha_scale,
                                                backend):
    """The fast kind's (f, beta), read in float64, costs at most 2.5e-4 (the
    benchmark's ``ra_gap`` limit) more than the benchmark's NumPy optimum
    of (18) and than the exact solver's, in every regime: f at f_min, f
    interior and f at f_max."""
    reference = _bench_reference()
    sc, groups = _regime_groups(lambda_t, alpha_scale, seed=4)
    cb, masks = _batched(sc, groups)
    fast = ra.solve_fixed_point_batched(cb, masks, backend=backend,
                                        **ra.SCREEN_PROFILES["default"])
    exact = jax.vmap(ra.solve_exact)(cb, masks)
    model = reference.Model(sc)

    def priced(sol):
        return np.array([reference.group_objective(
            model, [g], np.asarray(sol.f[i]), np.asarray(sol.beta[i]))[0]
            for i, g in enumerate(groups)])

    opt = (reference.solve_groups(model, groups)[0]
           - model.cloud[[s for s, _ in groups]])
    got = priced(fast)
    assert np.all((got - opt) / opt <= 2.5e-4), (got - opt) / opt
    assert np.all((got - priced(exact)) / got <= 2.5e-4)
    # the regime: some devices clipped at f_max (cheap computing) or at
    # f_min (the others)
    m = np.asarray(masks)
    f = np.asarray(fast.f)[m]
    if alpha_scale < 1.0:
        assert (f >= np.asarray(cb.f_max)[m] * (1 - 1e-6)).any()
    else:
        assert (f <= np.asarray(cb.f_min)[m] * (1 + 1e-6)).any()
    assert np.all(np.abs(np.asarray(fast.beta).sum(axis=1) - 1.0) < 1e-5)
