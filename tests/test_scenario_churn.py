"""Dynamic scenarios: seeded perturbations (`perturb_scenario`), incremental
reach-map maintenance (`update_reach_index` / `update_reach_buckets`), and
the fast engine's warm-started `rerun_incremental` parity with a cold
rebuild."""

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import make_scenario, perturb_scenario
from repro.core.assoc_fast import FastAssociationEngine
from repro.core.scenario import (reach_index_map, update_reach_buckets,
                                 update_reach_index)

CHURN = dict(drift_m=80.0, move_frac=0.2, flip_frac=0.1, depart_frac=0.15)


# ---------------------------------------------------------------------------
# perturb_scenario
# ---------------------------------------------------------------------------

def test_perturb_deterministic_and_pure():
    sc = make_scenario(20, 4, seed=1, reach_m=300.0)
    avail0 = sc.avail.copy()
    a, da = perturb_scenario(sc, seed=7, **CHURN)
    b, db = perturb_scenario(sc, seed=7, **CHURN)
    np.testing.assert_array_equal(a.dist, b.dist)
    np.testing.assert_array_equal(a.avail, b.avail)
    np.testing.assert_array_equal(a.active_mask, b.active_mask)
    np.testing.assert_array_equal(da.stale_servers, db.stale_servers)
    np.testing.assert_array_equal(da.moved, db.moved)
    # the input scenario is untouched
    np.testing.assert_array_equal(sc.avail, avail0)
    assert sc.active is None
    # a different seed perturbs differently
    c, _ = perturb_scenario(sc, seed=8, **CHURN)
    assert not (np.array_equal(a.dist, c.dist)
                and np.array_equal(a.active_mask, c.active_mask))


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_active_devices_always_reach_a_server(seed):
    """Constraint (17e) must survive ANY delta: every active device keeps at
    least one effectively reachable server, even under heavy simultaneous
    drift + flips + departures, and across chained perturbations."""
    sc = make_scenario(24, 5, seed=seed, reach_m=250.0)
    for step in range(4):
        sc, delta = perturb_scenario(
            sc, seed=100 * seed + step, drift_m=120.0, move_frac=0.4,
            flip_frac=0.3, depart_frac=0.2, arrive_frac=0.5)
        eff = sc.eff_avail
        act = sc.active_mask
        assert eff.any(axis=0)[act].all()
        # the maps the engine builds from this must therefore exist
        reach_index_map(sc.avail, active=act)
        reach_index_map(sc.avail, bucketed=True, active=act)
        # delta bookkeeping is self-consistent
        assert not (delta.arrived & delta.departed).any()
        assert (delta.stale_servers | ~delta.eff_flips.any(axis=1)).all()


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 100_000), drift=st.floats(0.0, 150.0),
       flip=st.floats(0.0, 0.5))
def test_every_device_keeps_raw_reach_after_perturb(seed, drift, flip):
    """The 17e repair covers EVERY device, not just the active ones: an
    inactive device whose reach the flips wiped out used to come back with
    an all-``False`` column, and its later re-arrival silently landed on
    server 0 via the masked argmin. Now the perturbation itself restores
    the nearest server, so raw reach is a scenario-wide invariant."""
    sc = make_scenario(20, 4, seed=3, reach_m=220.0)
    for step in range(3):
        sc, _ = perturb_scenario(
            sc, seed=seed + step, drift_m=drift, move_frac=0.3,
            flip_frac=flip, depart_frac=0.3, arrive_frac=0.4)
        assert sc.avail.any(axis=0).all(), (
            "a device lost its last raw-reachable server after perturb")
        # active devices additionally keep EFFECTIVE reach (17e proper)
        assert sc.eff_avail.any(axis=0)[sc.active_mask].all()


def test_perturb_holds_device_params_fixed():
    """Cost-model constants must be delta-invariant (the incremental cache
    contract): only dist/avail/active may change, and untouched dist
    columns stay bit-identical."""
    sc = make_scenario(20, 4, seed=2, reach_m=300.0)
    sc2, delta = perturb_scenario(sc, seed=9, **CHURN)
    assert sc2.dev is sc.dev and sc2.srv is sc.srv and sc2.lp is sc.lp
    unmoved = ~delta.moved
    np.testing.assert_array_equal(sc.dist[:, unmoved], sc2.dist[:, unmoved])
    assert (sc.dist[:, delta.moved] != sc2.dist[:, delta.moved]).any()
    np.testing.assert_array_equal(delta.avail_flips, sc.avail != sc2.avail)


def test_perturb_requires_positions():
    sc = make_scenario(8, 2, seed=0)
    sc.dev_xy = None
    with pytest.raises(ValueError, match="positions"):
        perturb_scenario(sc, seed=0)


# ---------------------------------------------------------------------------
# incremental reach maps
# ---------------------------------------------------------------------------

def _assert_flat_consistent(ri, eff):
    k, n = eff.shape
    for s in range(k):
        reach = np.flatnonzero(eff[s])
        np.testing.assert_array_equal(ri.idx[s, ri.valid[s]], reach)
        np.testing.assert_array_equal(ri.slot[s, reach],
                                      np.arange(reach.size))
        assert (ri.slot[s, ~eff[s]] == ri.r_max).all()


def _assert_buckets_consistent(rbk, eff):
    k, n = eff.shape
    seen = np.zeros(k, dtype=int)
    for b, bucket in enumerate(rbk.buckets):
        assert bucket.width <= rbk.r_max
        for row, srv in enumerate(bucket.servers):
            seen[srv] += 1
            assert rbk.bucket_of[srv] == b and rbk.row_of[srv] == row
            reach = np.flatnonzero(eff[srv])
            assert reach.size <= bucket.width
            assert bucket.valid[row, :reach.size].all()
            assert not bucket.valid[row, reach.size:].any()
            np.testing.assert_array_equal(bucket.idx[row, :reach.size],
                                          reach)
            np.testing.assert_array_equal(rbk.slot[srv, reach],
                                          np.arange(reach.size))
            # the sentinel must be rejected by every bucket's slot test
            assert (rbk.slot[srv, ~eff[srv]] >= bucket.width).all()
            assert bucket.key == max(reach.size - 1, 0).bit_length()
    assert (seen == 1).all(), "buckets must partition the servers"


def test_update_reach_index_patch_and_rebuild():
    # sparse enough that the allocated width (the widest count plus
    # headroom, in whole 128-lane tiles) stays below N
    sc = make_scenario(400, 8, seed=3, reach_m=150.0)
    ri = reach_index_map(sc.avail)
    assert ri.widest < ri.r_max < sc.n_devices
    sc2, delta = perturb_scenario(sc, seed=11, **CHURN)
    ri2, rebuilt = update_reach_index(ri, sc2.avail,
                                      active=sc2.active_mask,
                                      changed_servers=delta.stale_servers)
    _assert_flat_consistent(ri2, sc2.eff_avail)
    # shrinking reach never rebuilds (the allocated width is kept) ...
    if not rebuilt:
        assert ri2.r_max == ri.r_max
    # ... nor does growth inside the headroom ...
    avail = sc.avail.copy()
    far = np.flatnonzero(~avail[0])[:ri.r_max - avail[0].sum()]
    avail[0, far] = True                    # server 0 fills its width
    ri_full, rebuilt_full = update_reach_index(ri, avail)
    assert not rebuilt_full and ri_full.r_max == ri.r_max
    _assert_flat_consistent(ri_full, avail)
    # ... and growth past the allocated width rebuilds from scratch
    avail = sc.avail.copy()
    avail[0, :] = True                      # server 0 now reaches everyone
    ri3, rebuilt3 = update_reach_index(ri, avail)
    assert rebuilt3 and ri3.r_max == sc.n_devices
    _assert_flat_consistent(ri3, avail)


def test_update_reach_buckets_patch_keeps_untouched_arrays():
    """A within-bucket count change patches rows; buckets the delta never
    touches keep their arrays object-identical (that is what preserves the
    compiled sweep shapes and cached toggle rows across small deltas)."""
    # synthetic reach: counts 4 / 8 / 16 -> binary keys 2 / 3 / 4
    avail = np.zeros((3, 16), dtype=bool)
    avail[0, :4] = True
    avail[1, :8] = True
    avail[2, :] = True
    rbk = reach_index_map(avail, bucketed=True)
    assert [b.key for b in rbk.buckets] == [2, 3, 4]
    # server 1: 8 -> 7 stays inside key 3 and width 8 -> pure row patch
    avail2 = avail.copy()
    avail2[1, 7] = False
    rbk2, carry = update_reach_buckets(rbk, avail2)
    assert carry == [0, 1, 2]
    _assert_buckets_consistent(rbk2, avail2)
    assert rbk2.buckets[0].idx is rbk.buckets[0].idx     # untouched
    assert rbk2.buckets[2].idx is rbk.buckets[2].idx     # untouched
    assert rbk2.buckets[1].idx is not rbk.buckets[1].idx  # patched copy
    assert rbk2.buckets[1].width == rbk.buckets[1].width


def test_update_reach_buckets_overflow_rebuilds_only_crossed_buckets():
    """Crossing a binary bucket boundary (key change) rebuilds exactly the
    buckets the server leaves and joins; the result matches a from-scratch
    rebuild semantically (and here bit-identically, since the rebuilt
    widths coincide)."""
    avail = np.zeros((3, 16), dtype=bool)
    avail[0, :4] = True
    avail[1, :8] = True
    avail[2, :] = True
    rbk = reach_index_map(avail, bucketed=True)
    # server 0: 4 -> 6 crosses key 2 -> 3; bucket key2 empties (dropped),
    # bucket key3 absorbs server 0; bucket key4 must be untouched
    avail2 = avail.copy()
    avail2[0, 4:6] = True
    rbk2, carry = update_reach_buckets(rbk, avail2)
    _assert_buckets_consistent(rbk2, avail2)
    assert carry == [None, 2]
    assert rbk2.buckets[1].idx is rbk.buckets[2].idx
    fresh = reach_index_map(avail2, bucketed=True)
    assert len(rbk2.buckets) == len(fresh.buckets)
    for inc, ref in zip(rbk2.buckets, fresh.buckets):
        np.testing.assert_array_equal(inc.servers, ref.servers)
        np.testing.assert_array_equal(inc.idx, ref.idx)
        np.testing.assert_array_equal(inc.valid, ref.valid)
        assert (inc.width, inc.key) == (ref.width, ref.key)
    np.testing.assert_array_equal(rbk2.bucket_of, fresh.bucket_of)
    np.testing.assert_array_equal(rbk2.row_of, fresh.row_of)
    np.testing.assert_array_equal(rbk2.slot, fresh.slot)


def test_update_reach_buckets_sentinel_grows_monotonically():
    """When the widest bucket overflows, the shared out-of-reach sentinel
    grows and every stale sentinel entry is remapped — `slot < width` must
    stay a sound validity test for all servers."""
    avail = np.zeros((3, 16), dtype=bool)
    avail[0, :4] = True
    avail[1, :8] = True
    avail[1, 12:] = True  # keep every device reachable somewhere
    avail[2, :12] = True
    rbk = reach_index_map(avail, bucketed=True)
    assert rbk.r_max == 12
    avail2 = avail.copy()
    avail2[2, :] = True   # server 2: 16 devices, past the old r_max
    rbk2, _ = update_reach_buckets(rbk, avail2)
    assert rbk2.r_max == 16 > rbk.r_max
    _assert_buckets_consistent(rbk2, avail2)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_incremental_buckets_match_rebuilt_under_churn(seed):
    """Chained perturbations: the incrementally maintained maps must stay
    semantically identical to from-scratch maps of every perturbed state."""
    sc = make_scenario(24, 5, seed=seed, reach_m=250.0)
    rbk = reach_index_map(sc.avail, bucketed=True)
    ri = reach_index_map(sc.avail)
    for step in range(3):
        sc, delta = perturb_scenario(
            sc, seed=10 * seed + step, drift_m=120.0, move_frac=0.3,
            flip_frac=0.2, depart_frac=0.15, arrive_frac=0.3)
        act = sc.active_mask
        rbk, _ = update_reach_buckets(rbk, sc.avail, active=act,
                                      changed_servers=delta.stale_servers)
        ri, rebuilt = update_reach_index(ri, sc.avail, active=act,
                                         changed_servers=delta.stale_servers)
        eff = sc.eff_avail
        _assert_buckets_consistent(rbk, eff)
        _assert_flat_consistent(ri, eff)


# ---------------------------------------------------------------------------
# warm-started rerun_incremental vs cold rebuild
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("compact", [False, True, "bucketed"])
def test_rerun_incremental_matches_cold_rebuild(compact):
    """The hard parity gate: the warm-started stable point must be
    bit-identical to a cold rebuild descending from the same repaired
    assignment (verify=True raises otherwise), in every sweep space."""
    sc = make_scenario(18, 4, seed=0, reach_m=300.0)
    eng = FastAssociationEngine(sc, kind="fast", seed=0, compact=compact)
    eng.run("nearest", exchange_samples=0)
    sc2, delta = perturb_scenario(sc, seed=5, **CHURN)
    warm = eng.rerun_incremental(sc2, delta, exchange_samples=0, verify=True)
    # the warm stable point is genuinely stable: rerunning applies nothing
    again = FastAssociationEngine(sc2, kind="fast", seed=0,
                                  compact=compact).run(
        assignment=warm.assignment, exchange_samples=0)
    assert again.n_adjustments == 0
    # and every active device sits within effective reach
    eff = sc2.eff_avail
    for dev in np.flatnonzero(sc2.active_mask):
        assert eff[warm.assignment[dev], dev]


@pytest.mark.slow
def test_rerun_incremental_chained_with_arrivals():
    sc = make_scenario(18, 4, seed=1, reach_m=300.0)
    eng = FastAssociationEngine(sc, kind="fast", seed=0, compact=True)
    eng.run("nearest", exchange_samples=0)
    sc1, d1 = perturb_scenario(sc, seed=2, drift_m=80.0, move_frac=0.2,
                               depart_frac=0.3)
    assert d1.departed.sum() > 0
    r1 = eng.rerun_incremental(sc1, d1, exchange_samples=0, verify=True)
    # departed devices are in no group and carry no resources
    inact = np.flatnonzero(~sc1.active_mask)
    assert inact.size and (r1.f[inact] == 0).all()
    assert (r1.beta[inact] == 0).all()
    sc2, d2 = perturb_scenario(sc1, seed=3, drift_m=80.0, move_frac=0.2,
                               arrive_frac=1.0)
    assert d2.arrived.sum() > 0
    r2 = eng.rerun_incremental(sc2, d2, exchange_samples=0, verify=True)
    assert sc2.active_mask.all()
    assert (r2.f > 0).all()


@pytest.mark.slow
def test_rerun_incremental_after_tiered_run():
    sc = make_scenario(16, 4, seed=2, reach_m=300.0)
    eng = FastAssociationEngine(sc, kind="fast", seed=0, compact=True)
    eng.run_tiered("nearest", exchange_samples=0)
    sc2, delta = perturb_scenario(sc, seed=4, **CHURN)
    res = eng.rerun_incremental(sc2, delta, exchange_samples=0, verify=True)
    assert np.isfinite(res.total_cost) and res.total_cost > 0


def test_rerun_incremental_requires_prior_run():
    sc = make_scenario(10, 3, seed=0, reach_m=300.0)
    eng = FastAssociationEngine(sc, kind="fast", seed=0)
    sc2, delta = perturb_scenario(sc, seed=1, move_frac=0.2)
    with pytest.raises(RuntimeError, match="prior run"):
        eng.rerun_incremental(sc2, delta)


def test_reference_engine_active_parity_on_churn_scenario():
    """The host reference engine must honour the active mask exactly like
    the fast engine: inactive devices in no group, zero resources, and the
    deterministic steepest-descent stable points must coincide."""
    from repro.core.edge_association import AssociationEngine
    sc = make_scenario(14, 3, seed=4, reach_m=300.0)
    sc1, _ = perturb_scenario(sc, seed=2, move_frac=0.0, depart_frac=0.25)
    dead = np.flatnonzero(~sc1.active_mask)
    assert dead.size > 0
    ref = AssociationEngine(sc1, kind="fast", seed=0).run_batched(
        "nearest", exchange_samples=0)
    fast = FastAssociationEngine(sc1, kind="fast", seed=0).run(
        "nearest", exchange_samples=0)
    assert np.array_equal(ref.assignment, fast.assignment)
    assert abs(ref.total_cost - fast.total_cost) <= 1e-4 * fast.total_cost
    assert (ref.f[dead] == 0).all() and (ref.beta[dead] == 0).all()
    assert np.isfinite(ref.true_cost)


@pytest.mark.slow
def test_exchanges_never_move_inactive_and_respect_binding_caps():
    """PR-10 satellite regression. ``do_exchange`` samples device pairs
    uniformly from ``[0, n)`` with no explicit active gate — the only thing
    standing between a parked device and an escape move is ``can_join``'s
    ``ex_bucket.ok`` mask, which is derived from ``eff_avail`` (active-
    masked) in every sweep space. Pin that, plus cap-neutrality: exchanges
    are 1-for-1 swaps, so per-server loads are unchanged by construction and
    a binding ``capacity`` can never be violated by the escape path.

    Transfers only ever move active devices (sweep rows are active-masked
    at bucket build time) and both runs share the same init, so any
    divergence at an inactive index would implicate an exchange."""
    sc = make_scenario(16, 4, seed=1, reach_m=300.0, cap_slack=1.2)
    sc1, _ = perturb_scenario(sc, seed=2, move_frac=0.0, depart_frac=0.25)
    dead = np.flatnonzero(~sc1.active_mask)
    assert dead.size > 0 and sc1.capacity is not None

    def cold(samples):
        return FastAssociationEngine(
            sc1, kind="fast", seed=0, compact="bucketed").run(
            "nearest", exchange_samples=samples)

    no_ex, ex = cold(0), cold(64)
    assert ex.n_adjustments > no_ex.n_adjustments  # escape path fired
    np.testing.assert_array_equal(ex.assignment[dead],
                                  no_ex.assignment[dead])
    load = np.bincount(ex.assignment[sc1.active_mask],
                       minlength=sc1.n_servers)
    assert (load <= sc1.capacity).all()
    assert (load == sc1.capacity).any()  # the caps genuinely bind

    # the churn-tick warm path carries the same contract: identical prior
    # engines, one rerun transfer-only and one with exchanges, both under
    # the verify (cold-rebuild parity) gate
    sc2, d2 = perturb_scenario(sc1, seed=3, drift_m=60.0, move_frac=0.2,
                               flip_frac=0.1, depart_frac=0.15,
                               arrive_frac=0.3)
    dead2 = np.flatnonzero(~sc2.active_mask)
    assert dead2.size > 0
    warms = []
    for samples in (0, 64):
        eng = FastAssociationEngine(sc1, kind="fast", seed=0,
                                    compact="bucketed")
        eng.run("nearest", exchange_samples=64)
        warms.append(eng.rerun_incremental(sc2, d2, exchange_samples=samples,
                                           verify=True))
    np.testing.assert_array_equal(warms[1].assignment[dead2],
                                  warms[0].assignment[dead2])
    wload = np.bincount(warms[1].assignment[sc2.active_mask],
                        minlength=sc2.n_servers)
    assert (wload <= sc2.capacity).all()


def test_churn_scenario_cold_run_excludes_inactive():
    """A fresh engine on a churn scenario must park inactive devices with
    zero cost contribution: dropping them entirely from the scenario yields
    the same total cost."""
    sc = make_scenario(16, 4, seed=3, reach_m=300.0)
    sc1, d1 = perturb_scenario(sc, seed=6, move_frac=0.0, depart_frac=0.25)
    dead = np.flatnonzero(~sc1.active_mask)
    assert dead.size > 0
    res = FastAssociationEngine(sc1, kind="fast", seed=0, compact=True).run(
        "nearest", exchange_samples=0)
    member = np.zeros((sc.n_servers, sc.n_devices), dtype=bool)
    member[res.assignment, np.arange(sc.n_devices)] = True
    assert not member[:, dead].any() or (res.f[dead] == 0).all()
    base = FastAssociationEngine(sc, kind="fast", seed=0, compact=True).run(
        "nearest", exchange_samples=0)
    assert res.total_cost < base.total_cost  # fewer active devices
