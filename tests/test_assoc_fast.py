"""Device-resident association engine: parity with the host reference,
permission semantics, and toggle-cache consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_scenario
from repro.core import resource_allocation as ra
from repro.core.assoc_fast import FastAssociationEngine
from repro.core.edge_association import AssociationEngine
from repro.core.scenario import make_large_scenario

PARITY_CASES = [(14, 3, 0), (18, 4, 1), (16, 4, 2)]


@pytest.mark.parametrize("n,k,seed", PARITY_CASES)
def test_parity_with_reference_stable_point(n, k, seed):
    """With exchanges disabled both engines are deterministic steepest
    transfer descent and must land on the same stable point (the PR's
    1e-4 parity gate); with exchanges the fast engine must not be worse."""
    sc = make_scenario(n, k, seed=seed)
    ref = AssociationEngine(sc, kind="fast", seed=0).run_batched(
        "nearest", exchange_samples=0)
    fast = FastAssociationEngine(sc, kind="fast", seed=0).run(
        "nearest", exchange_samples=0)
    assert abs(fast.total_cost - ref.total_cost) <= 1e-4 * ref.total_cost
    assert fast.total_cost <= ref.total_cost + 1e-4 * ref.total_cost
    # steepest descent with identical tie-breaking: same stable assignment
    assert np.array_equal(fast.assignment, ref.assignment)


def test_parity_with_exchanges_not_worse():
    sc = make_scenario(16, 4, seed=3)
    ref = AssociationEngine(sc, kind="fast", seed=0).run_batched("nearest")
    fast = FastAssociationEngine(sc, kind="fast", seed=0).run("nearest")
    # exchange sampling differs (numpy vs jax PRNG); both must reach a
    # stable point no worse than a few percent of each other
    assert fast.total_cost <= ref.total_cost * 1.02


@pytest.mark.slow
def test_permission_semantics_match_reference_move_for_move():
    """Tiny fixture, no exchanges: the fast engine must replicate the
    reference engine's applied moves exactly under both permission rules."""
    sc = make_scenario(10, 3, seed=7)
    for permission in ("utilitarian", "pareto"):
        ref = AssociationEngine(sc, kind="fast", permission=permission,
                                seed=0).run_batched("nearest",
                                                    exchange_samples=0)
        fast = FastAssociationEngine(sc, kind="fast", permission=permission,
                                     seed=0).run("nearest",
                                                 exchange_samples=0)
        assert fast.n_adjustments == ref.n_adjustments, permission
        assert np.array_equal(fast.assignment, ref.assignment), permission
        np.testing.assert_allclose(np.asarray(fast.cost_trace),
                                   np.asarray(ref.cost_trace),
                                   rtol=1e-4)


def test_pareto_at_most_utilitarian_moves():
    sc = make_scenario(12, 3, seed=5)
    ut = FastAssociationEngine(sc, kind="fast", permission="utilitarian",
                               seed=0).run("random", exchange_samples=0)
    pa = FastAssociationEngine(sc, kind="fast", permission="pareto",
                               seed=0).run("random", exchange_samples=0)
    assert pa.n_adjustments <= ut.n_adjustments


def test_toggle_cache_matches_uncached_solves():
    """The incremental bitset/toggle cache must agree with from-scratch
    group solves at the stable point — both the current-group costs and a
    sample of single-device-toggled variants."""
    sc = make_scenario(12, 3, seed=4)
    eng = FastAssociationEngine(sc, kind="fast", seed=0)
    eng.run("nearest", exchange_samples=0)
    st = eng.last_state
    member = st["member"]
    k, n = member.shape
    cloud = np.asarray(eng.cloud_const)

    def fresh_cost(server, mask):
        sol = eng.solver.solve_batch(np.array([server]), mask[None, :])
        base = float(np.asarray(sol.cost)[0])
        return base + (cloud[server] if mask.any() else 0.0)

    for s in range(k):
        assert fresh_cost(s, member[s]) == pytest.approx(
            float(st["cur_cost"][s]), rel=1e-5, abs=1e-6)
    rng = np.random.default_rng(0)
    for s, d in zip(rng.integers(0, k, 6), rng.integers(0, n, 6)):
        toggled = member[s].copy()
        toggled[d] = ~toggled[d]
        assert fresh_cost(s, toggled) == pytest.approx(
            float(st["toggle_cost"][s, d]), rel=1e-5, abs=1e-6)


def test_monotone_trace_stability_and_availability():
    sc = make_scenario(18, 4, seed=0, reach_m=300.0)
    eng = FastAssociationEngine(sc, kind="fast", seed=0)
    res = eng.run("random")
    trace = np.asarray(res.cost_trace)
    assert np.all(np.diff(trace) <= 1e-6 * trace[:-1]), "cost must decrease"
    avail = np.asarray(sc.avail)
    for dev, srv in enumerate(res.assignment):
        assert avail[srv, dev]
    # stability: restarting from the stable point applies no adjustment
    res2 = FastAssociationEngine(sc, kind="fast", seed=0).run(
        assignment=res.assignment)
    assert res2.n_adjustments == 0


def test_dense_path_is_identity_bucket():
    """The dense sweep must be the unified kernel configured with identity
    index maps — idx[k] = arange(N), every slot exists, candidacy gated by
    avail — not a separate code path."""
    sc = make_scenario(12, 3, seed=1, reach_m=300.0)
    eng = FastAssociationEngine(sc, kind="fast", seed=0, compact=False)
    assert len(eng._buckets) == 1
    b = eng._buckets[0]
    n, k = sc.n_devices, sc.n_servers
    np.testing.assert_array_equal(
        np.asarray(b.idx), np.tile(np.arange(n), (k, 1)))
    assert np.asarray(b.exists).all()
    np.testing.assert_array_equal(np.asarray(b.ok), np.asarray(sc.avail))
    np.testing.assert_array_equal(
        np.asarray(eng._slot_of), np.tile(np.arange(n), (k, 1)))


@pytest.mark.parametrize("compact", [False, True, "bucketed"])
def test_identity_and_slot_maps_move_for_move_vs_reference(compact):
    """Every sweep-space configuration of the unified kernel must reproduce
    the host reference engine's applied moves exactly at
    ``exchange_samples=0`` (the PR-1 dense gate, now covering all maps)."""
    sc = make_scenario(16, 4, seed=2, reach_m=300.0)
    ref = AssociationEngine(sc, kind="fast", seed=0).run_batched(
        "nearest", exchange_samples=0)
    fast = FastAssociationEngine(sc, kind="fast", seed=0, compact=compact).run(
        "nearest", exchange_samples=0)
    assert fast.n_adjustments == ref.n_adjustments
    assert np.array_equal(fast.assignment, ref.assignment)
    np.testing.assert_allclose(np.asarray(fast.cost_trace),
                               np.asarray(ref.cost_trace), rtol=1e-4)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_optimal_kind_move_for_move_vs_reference_at_exact_cost(seed):
    """The optimal kind replicates the host reference engine's moves on a
    small dense scenario, and at the paper's point (N=60, K=5) its
    finalized ``total_cost`` equals the exact solver's pricing of the same
    stable assignment within 1e-5 relative."""
    sc = make_scenario(14, 3, seed=seed)
    ref = AssociationEngine(sc, kind="optimal", seed=0).run_batched(
        "nearest", exchange_samples=0)
    fast = FastAssociationEngine(sc, kind="optimal", seed=0,
                                 compact=False).run("nearest",
                                                    exchange_samples=0)
    assert fast.n_adjustments == ref.n_adjustments
    assert np.array_equal(fast.assignment, ref.assignment)
    np.testing.assert_allclose(np.asarray(fast.cost_trace),
                               np.asarray(ref.cost_trace), rtol=1e-4)

    sc = make_scenario(60, 5, seed=seed)
    eng = FastAssociationEngine(sc, kind="optimal", seed=0)
    res = eng.run("nearest")
    member = np.zeros((5, 60), bool)
    member[res.assignment, np.arange(60)] = True
    exact = jax.vmap(ra.solve_exact)(eng.solver.consts, jnp.asarray(member))
    total = float(np.sum(np.asarray(exact.cost, np.float64)
                         + np.where(member.any(axis=1),
                                    np.asarray(eng.cloud_const), 0.0)))
    assert abs(res.total_cost - total) <= 1e-5 * total


def test_large_scenario_generator_shapes():
    sc = make_large_scenario(2000, 50, seed=0)
    assert sc.n_devices == 2000 and sc.n_servers == 50
    assert sc.avail.shape == (50, 2000)
    assert sc.avail.any(axis=0).all(), "every device reaches some server"
    # sparse availability: restricted reach keeps the candidate set local
    assert sc.avail.mean() < 0.5


def test_scheme_kinds_run_on_fast_engine():
    sc = make_scenario(10, 3, seed=6)
    for kind in ("comp_only", "uniform", "proportional"):
        res = FastAssociationEngine(sc, kind=kind, seed=0).run(
            "nearest", exchange_samples=8)
        assert np.isfinite(res.total_cost) and res.total_cost > 0
