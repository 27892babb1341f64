"""Sharded association sweep + fused golden-section kernel: parity of the
shard_map candidate refresh with the classic single-device engine (the PR's
bit-exactness contract), kernel-vs-reference parity in interpret mode, and
the memory-safe chunked distance construction.

Multi-device cases need ``XLA_FLAGS=--xla_force_host_platform_device_count``
(exported by ``scripts/tier1.sh``) and skip on a single-device run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_scenario
from repro.core import resource_allocation as ra
from repro.core.assoc_fast import FastAssociationEngine
from repro.core.scenario import (make_large_scenario, pairwise_dist,
                                 perturb_scenario)
from repro.kernels import ops, ref

N_DEV = len(jax.devices())
multi_device = pytest.mark.skipif(
    N_DEV < 2, reason="needs XLA_FLAGS=--xla_force_host_platform_device_"
                      "count (scripts/tier1.sh exports it)")


def _batched_consts(g=8, r=16, seed=0):
    """(G, R) RAConstants batch + masks built by jittering one server's
    constants (same factor on f_min/f_max keeps the box ordered)."""
    from repro.core.cost_model import ra_constants
    sc = make_scenario(r, 2, seed=seed)
    c = ra_constants(sc.dev, sc.srv.bandwidth[0], sc.srv.noise[0], sc.lp)
    key = jax.random.key(seed + 13)
    scale = jax.random.uniform(key, (g, 1), minval=0.7, maxval=1.3)
    cg = jax.tree.map(
        lambda x: (jnp.broadcast_to(jnp.asarray(x), (g,))
                   if jnp.asarray(x).ndim == 0
                   else jnp.asarray(x)[None, :] * scale), c)
    masks = jax.random.uniform(jax.random.key(seed + 29), (g, r)) < 0.7
    masks = masks.at[:, 0].set(True)          # no empty groups
    masks = masks.at[0].set(jnp.arange(r) == 0)   # singleton group edge case
    return cg, masks


@pytest.mark.parametrize("profile", sorted(ra.SCREEN_PROFILES))
def test_golden_kernel_matches_fixed_point(profile):
    """Fused kernel vs the scalar solver vmapped, at every screening
    profile — the documented parity pin is rtol 2e-4 on cost (interpret
    mode is in practice bit-identical; real-TPU fusion need not be)."""
    iters = ra.SCREEN_PROFILES[profile]
    cg, masks = _batched_consts(seed=1)
    oracle = jax.vmap(
        lambda cc, m: ra.solve_fixed_point(cc, m, **iters))(cg, masks)
    sol = ra.solve_fixed_point_batched(cg, masks, backend="pallas", **iters)
    np.testing.assert_allclose(sol.cost, oracle.cost, rtol=2e-4)
    np.testing.assert_allclose(sol.deadline, oracle.deadline, rtol=2e-4)
    np.testing.assert_allclose(sol.f, oracle.f, rtol=2e-4)
    np.testing.assert_allclose(sol.beta, oracle.beta, rtol=2e-4, atol=1e-7)


def test_golden_kernel_matches_ref():
    """Kernel (interpret mode) vs the plain-jnp reference formulation —
    same math, same iteration counts, so the gap must be float noise."""
    cg, masks = _batched_consts(g=6, r=12, seed=2)
    f, beta, cost, dl = ops.golden_section_solve(
        cg.a, cg.b, cg.d, cg.e, cg.w, cg.f_min, cg.f_max, masks,
        n_golden=16, n_price=4, n_root=3, n_bracket=16)
    f_r, beta_r, cost_r, dl_r = ref.golden_section_ref(
        cg.a, cg.b, cg.d, cg.e, cg.w, cg.f_min, cg.f_max, masks,
        n_golden=16, n_price=4, n_root=3, n_bracket=16)
    np.testing.assert_allclose(cost, cost_r, rtol=1e-6)
    np.testing.assert_allclose(dl, dl_r, rtol=1e-6)
    np.testing.assert_allclose(f, f_r, rtol=1e-6)
    np.testing.assert_allclose(beta, beta_r, rtol=1e-6, atol=1e-9)


def test_golden_kernel_block_padding():
    """G not a multiple of block_g: padded rows must not leak into the
    first G outputs."""
    cg, masks = _batched_consts(g=5, r=10, seed=3)
    full = ops.golden_section_solve(
        cg.a, cg.b, cg.d, cg.e, cg.w, cg.f_min, cg.f_max, masks,
        n_golden=16, n_price=4, n_root=3, n_bracket=16)
    blocked = ops.golden_section_solve(
        cg.a, cg.b, cg.d, cg.e, cg.w, cg.f_min, cg.f_max, masks,
        n_golden=16, n_price=4, n_root=3, n_bracket=16, block_g=4)
    for x, y in zip(full, blocked):
        np.testing.assert_allclose(x, y, rtol=1e-6)


def test_batched_xla_matches_scalar_solver():
    """backend="xla" is the scalar solver vmapped — per-group results must
    match solving each group alone."""
    iters = ra.SCREEN_PROFILES["coarse"]
    cg, masks = _batched_consts(g=4, r=8, seed=4)
    sol = ra.solve_fixed_point_batched(cg, masks, backend="xla", **iters)
    for i in range(4):
        one = ra.solve_fixed_point(jax.tree.map(lambda x: x[i], cg),
                                   masks[i], **iters)
        np.testing.assert_allclose(sol.cost[i], one.cost, rtol=1e-6)
        np.testing.assert_allclose(sol.f[i], one.f, rtol=1e-6)


PARITY_CASES = [(14, 3, 0), (18, 4, 1)]


@pytest.mark.parametrize("compact", ["bucketed", True, False])
def test_sharded_one_device_identical(compact):
    """A 1-device mesh routes through shard_map + the collective merge; the
    stable point must stay bit-identical to the classic in-process sweep."""
    sc = make_scenario(14, 3, seed=0, reach_m=300.0)
    classic = FastAssociationEngine(sc, kind="fast", seed=0,
                                    compact=compact).run(
        "nearest", exchange_samples=0)
    sharded = FastAssociationEngine(sc, kind="fast", seed=0, compact=compact,
                                    shards=1).run(
        "nearest", exchange_samples=0)
    assert np.array_equal(classic.assignment, sharded.assignment)
    assert classic.n_adjustments == sharded.n_adjustments
    assert sharded.total_cost == pytest.approx(classic.total_cost, rel=1e-6)


@multi_device
@pytest.mark.parametrize("n,k,seed", PARITY_CASES)
def test_sharded_multi_device_identical(n, k, seed):
    """k-device mesh: psum'd cache init + all_gather winner merge must
    reproduce the sequential bucket fold's move sequence exactly."""
    sc = make_scenario(n, k, seed=seed, reach_m=300.0)
    classic = FastAssociationEngine(sc, kind="fast", seed=0,
                                    compact="bucketed").run(
        "nearest", exchange_samples=0)
    sharded = FastAssociationEngine(sc, kind="fast", seed=0,
                                    compact="bucketed", shards=N_DEV).run(
        "nearest", exchange_samples=0)
    assert np.array_equal(classic.assignment, sharded.assignment)
    assert classic.n_adjustments == sharded.n_adjustments


@pytest.mark.slow
@multi_device
def test_sharded_warm_rerun_parity():
    """rerun_incremental on a sharded engine: warm stable point must match
    the classic engine's warm rerun AND pass its own verify gate (cold
    rebuild from the same repaired assignment)."""
    sc = make_large_scenario(120, 6, seed=5)
    classic = FastAssociationEngine(sc, kind="fast", seed=0,
                                    profile="coarse", compact="bucketed")
    classic.run("nearest", exchange_samples=0)
    sharded = FastAssociationEngine(sc, kind="fast", seed=0,
                                    profile="coarse", compact="bucketed",
                                    shards=N_DEV)
    sharded.run("nearest", exchange_samples=0)
    sc2, delta = perturb_scenario(sc, seed=6, drift_m=60.0, move_frac=0.05,
                                  flip_frac=0.02, depart_frac=0.02)
    warm_c = classic.rerun_incremental(sc2, delta, exchange_samples=0)
    warm_s = sharded.rerun_incremental(sc2, delta, exchange_samples=0,
                                       verify=True)
    assert np.array_equal(warm_c.assignment, warm_s.assignment)
    assert warm_c.n_adjustments == warm_s.n_adjustments


@pytest.mark.slow
def test_pallas_backend_engine_matches_xla():
    """ra_backend="pallas" swaps the refresh solver for the fused kernel;
    the stable point must agree within the kernel's documented tolerance
    (interpret mode lands bit-identical)."""
    sc = make_scenario(14, 3, seed=0, reach_m=300.0)
    xla = FastAssociationEngine(sc, kind="fast", seed=0,
                                compact="bucketed").run(
        "nearest", exchange_samples=0)
    pal = FastAssociationEngine(sc, kind="fast", seed=0, compact="bucketed",
                                ra_backend="pallas").run(
        "nearest", exchange_samples=0)
    assert np.array_equal(xla.assignment, pal.assignment)
    assert pal.total_cost == pytest.approx(xla.total_cost, rel=2e-4)


# The PR-10 contract matrix: sharded stable points AND per-move traces are
# bit-identical to the single-device engine across every sweep space ×
# shard count × exchange setting. The (16, 4, seed=1) geometry is the one
# the exchange tests pin (transfers alone stall short of the exchange-on
# stable point, so the escape path genuinely fires). In the dense and flat
# spaces the single-device engine refreshes both touched rows in one
# batched solve and the sharded one row by row, so these cases also hold
# the fused refresh to the unfused one, for the exact solver too.
EXCHANGE_MATRIX = ([(c, p, s, "fast")
                    for c in ("bucketed", True, False)
                    for p in (1, 3, 4)
                    for s in (0, 64)]
                   + [(c, 1, s, "optimal")
                      for c in (True, False)
                      for s in (0, 64)])
# counters whose value does not depend on the row padding of a mesh
SHARD_FREE_COUNTS = ("stale_rows", "iterations", "transfers",
                     "exchange_tries", "exchanges")


def _cache(eng):
    """The sweep's final cur/toggle cache, each bucket cut to its real rows
    (a sharded layout pads them at the end)."""
    warm = eng._warm_cache
    return warm["cur"], [t[:np.asarray(bd.servers < eng.sc.n_servers).sum()]
                         for t, bd in zip(warm["toggles"], eng._buckets)]


@pytest.mark.parametrize(
    "compact,shards,samples,kind", EXCHANGE_MATRIX,
    ids=[f"{'dense' if c is False else 'flat' if c is True else c}"
         f"-p{p}-ex{s}" + ("" if kd == "fast" else f"-{kd}")
         for c, p, s, kd in EXCHANGE_MATRIX])
def test_sharded_exchange_parity_matrix(compact, shards, samples, kind):
    """Distributed sampled exchanges (PR 10): the replicated pair proposal +
    chunk-partitioned pricing + all_gather (delta, sample-order) winner fold
    must reproduce the single-device exchange sequence bit-for-bit — same
    assignment, same move count, same per-move cost trace, same final
    toggle-cost cache and the same work counters."""
    if shards > N_DEV:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count")
    sc = make_scenario(16, 4, seed=1, reach_m=300.0)
    classic_eng = FastAssociationEngine(sc, kind=kind, seed=0,
                                        compact=compact)
    classic = classic_eng.run("nearest", exchange_samples=samples)
    sharded_eng = FastAssociationEngine(sc, kind=kind, seed=0,
                                        compact=compact, shards=shards)
    sharded = sharded_eng.run("nearest", exchange_samples=samples)
    assert np.array_equal(classic.assignment, sharded.assignment)
    assert classic.n_adjustments == sharded.n_adjustments
    assert classic.cost_trace == sharded.cost_trace  # per-move, bitwise
    (cur_c, tog_c), (cur_s, tog_s) = _cache(classic_eng), _cache(sharded_eng)
    assert np.array_equal(cur_c, cur_s)
    assert all(np.array_equal(a, b) for a, b in zip(tog_c, tog_s))
    a, b = classic_eng.last_counts, sharded_eng.last_counts
    same = (SHARD_FREE_COUNTS if shards > 1
            else [c for c in a if c != "fused_refreshes"])
    assert {c: a[c] for c in same} == {c: b[c] for c in same}
    single_bucket = compact != "bucketed"
    assert a["fused_refreshes"] == (classic.n_adjustments if single_bucket
                                    else 0)
    assert b["fused_refreshes"] == 0
    if samples:
        # the geometry guarantees the exchange branch fires: with exchanges
        # the descent moves strictly beyond the transfers-only stable point
        no_ex = FastAssociationEngine(sc, kind=kind, seed=0,
                                      compact=compact).run(
            "nearest", exchange_samples=0)
        assert classic.n_adjustments > no_ex.n_adjustments
        assert classic.total_cost < no_ex.total_cost * (1 - 1e-5)


@pytest.mark.slow
@multi_device
def test_sharded_warm_rerun_parity_with_exchanges():
    """The warm path carries the lifted restriction too: a sharded
    rerun_incremental with exchange_samples>0 matches the classic warm rerun
    bit-identically AND passes its own verify gate (cold rebuild from the
    same repaired assignment, exchanges on)."""
    sc = make_large_scenario(120, 6, seed=5)
    classic = FastAssociationEngine(sc, kind="fast", seed=0,
                                    profile="coarse", compact="bucketed")
    classic.run("nearest", exchange_samples=64)
    sharded = FastAssociationEngine(sc, kind="fast", seed=0,
                                    profile="coarse", compact="bucketed",
                                    shards=N_DEV)
    sharded.run("nearest", exchange_samples=64)
    sc2, delta = perturb_scenario(sc, seed=6, drift_m=60.0, move_frac=0.05,
                                  flip_frac=0.02, depart_frac=0.02)
    warm_c = classic.rerun_incremental(sc2, delta, exchange_samples=64)
    warm_s = sharded.rerun_incremental(sc2, delta, exchange_samples=64,
                                       verify=True)
    assert np.array_equal(warm_c.assignment, warm_s.assignment)
    assert warm_c.n_adjustments == warm_s.n_adjustments
    assert warm_c.cost_trace == warm_s.cost_trace


def test_sharded_constructor_validation():
    sc = make_scenario(14, 3, seed=0)
    with pytest.raises(ValueError):
        FastAssociationEngine(sc, kind="fast", seed=0, shards=0)
    with pytest.raises(ValueError):
        FastAssociationEngine(sc, kind="fast", seed=0, shards=N_DEV + 1)
    with pytest.raises(ValueError):
        FastAssociationEngine(sc, kind="fast", seed=0, ra_backend="mosaic")
    with pytest.raises(ValueError):
        FastAssociationEngine(sc, kind="exact", seed=0, ra_backend="pallas")


def test_pairwise_dist_chunked_bitwise():
    """Chunked distance computation must be bit-identical to the dense
    broadcast it replaces, including chunk sizes that straddle N."""
    rng = np.random.default_rng(0)
    srv = rng.uniform(0, 1000, (7, 2))
    dev = rng.uniform(0, 1000, (103, 2))
    dense = np.linalg.norm(srv[:, None, :] - dev[None, :, :], axis=-1)
    for chunk in (1, 13, 103, 200):
        assert np.array_equal(pairwise_dist(srv, dev, chunk=chunk), dense)
    assert pairwise_dist(srv, dev[:0]).shape == (7, 0)


@pytest.mark.slow
@multi_device
def test_sharded_n20000_converges():
    """N=20k/K=200 sharded convergence smoke: the regime cap lift + chunked
    construction + sharded sweep exist for. Coarse/loose-tol so the run
    stays minutes, not hours; asserts genuine stability (no move-cap
    exit)."""
    sc = make_large_scenario(20_000, 200, seed=0, spread_m=60.0)
    eng = FastAssociationEngine(sc, kind="fast", seed=0, profile="coarse",
                                rel_tol=1e-2, compact="bucketed",
                                shards=N_DEV)
    eng.run("nearest", max_moves=4000, exchange_samples=0, finalize=False)
    assert eng.last_moves < 4000
    assign = eng.stable_assignment
    avail = np.asarray(sc.avail)
    active = sc.active_mask
    assert assign is not None
    assert avail[assign[active], np.flatnonzero(active)].all()
