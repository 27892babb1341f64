"""End-to-end live co-simulation tier (repro.fl.live): elastic re-association
during federated training under device churn.

The load-bearing gates:
  * warm/cold swap parity — ``incremental-warm`` and ``periodic-cold`` must
    produce bit-identical assignments at every swap point (the PR-4 parity
    gate lifted into the training loop), hence identical cumulative eq.-(17)
    cost;
  * any re-association policy is at least as cheap (cumulative eq.-17) as
    the frozen ``static`` assignment on a churn scenario;
  * history shapes are stable across ``eval_every`` (round-indexed lists
    always span every round; eval-indexed lists carry their own index).
"""

import jax
import numpy as np
import pytest

from repro.core.assoc_fast import assignment_true_cost
from repro.core.scenario import (device_client_bridge, diff_scenarios,
                                 make_large_scenario, perturb_scenario)
from repro.data import make_mnist_like
from repro.fl import churn_tick, run_live
from repro.fl.live import LiveHFELRunner

N, K = 16, 3
ROUNDS = 4
# heavy churn so every policy decision matters within a handful of rounds
CHURN = dict(drift_m=60.0, move_frac=0.2, flip_frac=0.1, depart_frac=0.15,
             arrive_frac=0.5)


@pytest.fixture(scope="module")
def sc():
    return make_large_scenario(N, K, seed=0)


@pytest.fixture(scope="module")
def ds():
    return make_mnist_like(N, samples_total=400, seed=0)


def _live(sc, ds, policy, **kw):
    kw.setdefault("rounds", ROUNDS)
    kw.setdefault("resolve_every", 2)
    kw.setdefault("churn", CHURN)
    kw.setdefault("seed", 0)
    kw.setdefault("local_iters", 2)
    kw.setdefault("edge_iters", 2)
    return run_live(sc, ds, policy=policy, **kw)


# -- (a) warm/cold parity at every swap point --------------------------------

@pytest.mark.slow
def test_warm_and_cold_policies_swap_bit_identically(sc, ds):
    warm = _live(sc, ds, "incremental-warm")
    cold = _live(sc, ds, "periodic-cold")
    assert warm.swap_rounds == cold.swap_rounds
    assert warm.swap_rounds[0] == 0 and len(warm.swap_rounds) >= 2
    for r, aw, ac in zip(warm.swap_rounds, warm.swap_assignments,
                         cold.swap_assignments):
        np.testing.assert_array_equal(
            aw, ac, err_msg=f"swap assignments diverged at round {r}")
    # identical assignments on identical scenarios => identical costs
    np.testing.assert_allclose(warm.system_cost, cold.system_cost, rtol=1e-6)
    assert abs(warm.cumulative_cost - cold.cumulative_cost) <= (
        1e-6 * cold.cumulative_cost)


def test_incremental_warm_passes_engine_verify_gate(sc, ds):
    """verify=True runs the rerun_incremental cold-rebuild parity assertion
    inside every warm re-solve — it raising is the failure mode."""
    h = _live(sc, ds, "incremental-warm", verify=True)
    assert sum(h.swapped) >= 2


# -- (b) re-association beats (or ties) the frozen assignment ----------------

@pytest.mark.slow
def test_reassociation_cumulative_cost_beats_static(sc, ds):
    static = _live(sc, ds, "static")
    warm = _live(sc, ds, "incremental-warm", resolve_every=1)
    cold = _live(sc, ds, "periodic-cold", resolve_every=1)
    assert warm.cumulative_cost <= static.cumulative_cost * (1 + 1e-9)
    assert cold.cumulative_cost <= static.cumulative_cost * (1 + 1e-9)
    # static performs no descent after round 0
    assert static.moves[1:] == [0] * (static.rounds - 1)
    assert static.swap_rounds == [0]


def test_non_warm_policies_release_the_engine(sc, ds):
    """Only incremental-warm re-enters the round-0 engine; the others must
    not keep its toggle caches resident for the whole run."""
    from repro.fl.live import LiveHFELRunner
    runner = LiveHFELRunner(sc, N, policy="static", churn=CHURN, seed=0)
    h = run_live(sc, ds, policy="static", rounds=2, resolve_every=1,
                 churn=CHURN, seed=0, local_iters=1, edge_iters=1)
    assert h.rounds == 2   # ran fine without the engine
    tr = type("T", (), {"client_mask": None})()
    runner.begin_round(tr, 0)
    assert runner.engine is None


def test_per_round_cost_matches_standalone_evaluator(sc, ds):
    """history.system_cost[r] is assignment_true_cost of the round's
    assignment on the round's scenario — recompute round 0 independently."""
    h = _live(sc, ds, "static", rounds=1)
    e, t, c = assignment_true_cost(sc, h.swap_assignments[0])
    assert h.system_cost[0] == pytest.approx(c, rel=1e-6)
    assert h.system_energy[0] == pytest.approx(e, rel=1e-6)
    assert h.system_delay[0] == pytest.approx(t, rel=1e-6)


def test_churn_tick_replays_the_run_scenarios(sc, ds):
    """churn_tick depends on (seed, round) alone, so replaying it rebuilds
    every round's scenario: each swap assignment then checks against the
    scenario it was solved on (active count, reachability, eq.-17 cost)."""
    h = _live(sc, ds, "incremental-warm", resolve_every=1, rounds=3)
    scs = [sc]
    for r in range(1, h.rounds):
        scs.append(churn_tick(scs[-1], seed=0, r=r, churn=CHURN)[0])
    assert h.swap_rounds == [0, 1, 2]
    for r, assign in zip(h.swap_rounds, h.swap_assignments):
        act = np.flatnonzero(scs[r].active_mask)
        assert act.size == h.n_active[r]
        assert np.asarray(scs[r].eff_avail)[assign[act], act].all()
        _, _, c = assignment_true_cost(scs[r], assign)
        assert h.system_cost[r] == pytest.approx(c, rel=1e-6)


def test_true_cost_of_fully_departed_population_is_zero(sc):
    """Churn can legitimately empty a small scenario; the cost accounting
    must record a degenerate (0, 0, 0) round, not abort the simulation."""
    import dataclasses
    sc_empty = dataclasses.replace(sc, active=np.zeros(N, bool))
    assign = np.argmin(np.where(sc.avail, sc.dist, np.inf), axis=0)
    assert assignment_true_cost(sc_empty, assign) == (0.0, 0.0, 0.0)


def test_no_churn_degenerates_to_static(sc, ds):
    """With a zero-churn tick every policy keeps the round-0 stable point:
    no further moves, constant per-round cost."""
    none = dict(drift_m=0.0, move_frac=0.0, flip_frac=0.0, depart_frac=0.0,
                arrive_frac=0.0)
    static = _live(sc, ds, "static", churn=none, rounds=3)
    warm = _live(sc, ds, "incremental-warm", churn=none, rounds=3,
                 resolve_every=1)
    np.testing.assert_allclose(warm.system_cost, static.system_cost,
                               rtol=1e-6)
    assert warm.moves[1:] == [0, 0]
    np.testing.assert_allclose(static.system_cost,
                               [static.system_cost[0]] * 3, rtol=1e-6)


# -- (c) history shape stability across eval_every ---------------------------

@pytest.mark.parametrize("eval_every", [1, 2, 3])
def test_history_lengths_stable_across_eval_every(sc, ds, eval_every):
    h = _live(sc, ds, "incremental-warm", eval_every=eval_every, rounds=5)
    for name in ("system_cost", "system_energy", "system_delay",
                 "assoc_seconds", "swapped", "moves", "n_active",
                 "n_arrived", "n_departed"):
        assert len(getattr(h, name)) == 5, name
    expect_evals = sorted(set(range(0, 5, eval_every)) | {4})
    assert h.train.eval_rounds == expect_evals
    for name in ("test_acc", "train_acc", "train_loss"):
        assert len(getattr(h.train, name)) == len(expect_evals), name
    assert len(h.swap_rounds) == len(h.swap_assignments) == sum(h.swapped)
    d = h.as_dict()
    assert set(d["train"]) == {"test_acc", "train_acc", "train_loss",
                               "eval_rounds"}
    assert d["cumulative_cost"] == pytest.approx(sum(d["system_cost"]))


# -- bridge + delta-composition seams ----------------------------------------

def test_device_client_bridge_validates_and_maps(sc):
    b = device_client_bridge(sc, 10)
    np.testing.assert_array_equal(b.device_of, np.arange(10))
    assert b.n_clients == 10 and b.n_devices == N
    active = np.zeros(N, bool)
    active[[0, 3, 12]] = True
    np.testing.assert_array_equal(b.client_mask(active),
                                  active[:10])
    assign = np.arange(N) % K
    np.testing.assert_array_equal(b.client_assignment(assign), assign[:10])
    assert b.client_of[12] == -1 and b.client_of[3] == 3
    with pytest.raises(ValueError):
        device_client_bridge(sc, N + 1)
    with pytest.raises(ValueError):
        device_client_bridge(sc, 3, device_of=np.array([0, 0, 1]))
    with pytest.raises(ValueError):
        device_client_bridge(sc, 2, device_of=np.array([0, N]))


def test_live_runner_with_fewer_clients_than_devices(sc):
    """Deviceless clients are illegal; clientless devices are fine — the
    bridge masks them out of training while association still places them."""
    ds_small = make_mnist_like(10, samples_total=300, seed=1)
    h = run_live(sc, ds_small, policy="incremental-warm", rounds=2,
                 resolve_every=1, churn=CHURN, seed=0, local_iters=1,
                 edge_iters=1)
    assert h.rounds == 2 and len(h.swap_assignments[0]) == N


def test_diff_scenarios_matches_single_tick_delta(sc):
    sc2, delta = perturb_scenario(sc, seed=7, **CHURN)
    diff = diff_scenarios(sc, sc2)
    np.testing.assert_array_equal(diff.moved, delta.moved)
    np.testing.assert_array_equal(diff.arrived, delta.arrived)
    np.testing.assert_array_equal(diff.departed, delta.departed)
    np.testing.assert_array_equal(diff.avail_flips, delta.avail_flips)
    np.testing.assert_array_equal(diff.eff_flips, delta.eff_flips)
    np.testing.assert_array_equal(diff.stale_servers, delta.stale_servers)


def test_diff_scenarios_composes_two_ticks(sc):
    """The combined diff cancels a depart-then-return device and covers the
    union of both ticks' effective flips."""
    sc1, d1 = perturb_scenario(sc, seed=3, **CHURN)
    sc2, d2 = perturb_scenario(sc1, seed=4, **CHURN)
    diff = diff_scenarios(sc, sc2)
    returned = d1.departed & d2.arrived
    assert not (diff.departed & returned).any()
    assert not (diff.arrived & returned).any()
    np.testing.assert_array_equal(
        diff.eff_flips, sc2.eff_avail != sc.eff_avail)
    with pytest.raises(ValueError):
        diff_scenarios(sc, make_large_scenario(N + 1, K, seed=0))
    # same shape but unrelated scenario: device params differ -> every
    # incremental consumer's cached constants would be silently wrong
    with pytest.raises(ValueError, match="churn-invariant"):
        diff_scenarios(sc, make_large_scenario(N, K, seed=99))
    import dataclasses
    from repro.core.cost_model import LearningParams
    with pytest.raises(ValueError, match="churn-invariant"):
        diff_scenarios(sc, dataclasses.replace(
            sc2, lp=LearningParams(theta=0.25)))


def test_assignment_true_cost_rejects_mismatched_solver(sc):
    from repro.core.edge_association import GroupSolver
    assign = np.argmin(np.where(sc.avail, sc.dist, np.inf), axis=0)
    solver = GroupSolver(sc, "fast", seed=0, profile="default")
    with pytest.raises(ValueError, match="kind"):
        assignment_true_cost(sc, assign, solver=solver, kind="uniform")
    # a screening-profile solver is silently viewed at reference accuracy
    coarse = GroupSolver(sc, "fast", seed=0, profile="coarse")
    assert (assignment_true_cost(sc, assign, solver=coarse)
            == assignment_true_cost(sc, assign, solver=solver))


def test_stable_assignment_handoff_tracks_every_resolve(sc):
    """The engine's stable-point handoff surface: None before the first run,
    then always the latest stable assignment — after a cold run and after an
    incremental rerun (finalize=False fast path) alike."""
    from repro.core.assoc_fast import FastAssociationEngine
    eng = FastAssociationEngine(sc, kind="fast", seed=0, profile="coarse",
                                rel_tol=1e-3)
    assert eng.stable_assignment is None
    res = eng.run("nearest", exchange_samples=0)
    np.testing.assert_array_equal(eng.stable_assignment, res.assignment)
    sc2, delta = perturb_scenario(sc, seed=11, **CHURN)
    out = eng.rerun_incremental(sc2, delta, exchange_samples=0,
                                finalize=False)
    np.testing.assert_array_equal(eng.stable_assignment, out)
    assert eng.last_moves is not None and eng.last_moves >= 0


def test_runner_rejects_bad_config(sc):
    with pytest.raises(ValueError):
        LiveHFELRunner(sc, N, policy="nope")
    with pytest.raises(ValueError):
        LiveHFELRunner(sc, N, resolve_every=0)
    with pytest.raises(ValueError, match="maps 5 clients"):
        LiveHFELRunner(sc, 10, bridge=device_client_bridge(sc, 5))


# -- sharded engine plumbing (PR-6 follow-on) --------------------------------

def test_sharded_engine_plumbs_and_swaps_bit_identically(sc, ds):
    """shards=/ra_backend= reach every engine the policies construct, and a
    shards=1 live run keeps the bit-identical-assignment contract (hence an
    identical history) vs the classic single-device path."""
    runner = LiveHFELRunner(sc, N, shards=1, ra_backend="xla")
    eng = runner._new_engine(sc)
    assert eng.shards == 1 and eng.ra_backend == "xla"

    kw = dict(rounds=3, resolve_every=1, local_iters=1, edge_iters=1)
    base = _live(sc, ds, "incremental-warm", **kw)
    shard = _live(sc, ds, "incremental-warm", shards=1, **kw)
    assert shard.swap_rounds == base.swap_rounds
    for r, ab, ash in zip(base.swap_rounds, base.swap_assignments,
                          shard.swap_assignments):
        np.testing.assert_array_equal(
            ab, ash, err_msg=f"sharded swap diverged at round {r}")
    np.testing.assert_allclose(shard.system_cost, base.system_cost,
                               rtol=1e-6)


def test_sharded_live_with_exchanges_swaps_bit_identically(sc, ds):
    """PR 10 lifts the exchange_samples=0 sharding restriction: a sharded
    live run with sampled exchanges ON (the engine default) must keep the
    bit-identical-swap contract vs the classic single-device path — the
    replicated pair proposal + all_gather winner fold preserve the
    shards=None RNG stream exactly."""
    shards = min(3, len(jax.devices()))
    kw = dict(rounds=3, resolve_every=1, local_iters=1, edge_iters=1,
              exchange_samples=64)
    base = _live(sc, ds, "incremental-warm", **kw)
    shard = _live(sc, ds, "incremental-warm", shards=shards, verify=True,
                  **kw)
    assert shard.swap_rounds == base.swap_rounds
    for r, ab, ash in zip(base.swap_rounds, base.swap_assignments,
                          shard.swap_assignments):
        np.testing.assert_array_equal(
            ab, ash, err_msg=f"sharded exchange swap diverged at round {r}")
    np.testing.assert_allclose(shard.system_cost, base.system_cost,
                               rtol=1e-6)


# -- the larger configuration, slow tier -------------------------------------

@pytest.mark.slow
def test_live_parity_and_cost_larger_config():
    """N=64/K=6, more rounds, milder churn — the shape of the benchmark run,
    with verify ON inside every warm re-solve."""
    sc = make_large_scenario(64, 6, seed=1)
    ds = make_mnist_like(64, samples_total=1200, seed=1)
    churn = dict(drift_m=60.0, move_frac=0.08, flip_frac=0.03,
                 depart_frac=0.05, arrive_frac=0.3)
    kw = dict(rounds=6, resolve_every=2, churn=churn, seed=1, local_iters=2,
              edge_iters=2)
    warm = run_live(sc, ds, policy="incremental-warm", verify=True, **kw)
    cold = run_live(sc, ds, policy="periodic-cold", **kw)
    static = run_live(sc, ds, policy="static", **kw)
    assert warm.swap_rounds == cold.swap_rounds
    for aw, ac in zip(warm.swap_assignments, cold.swap_assignments):
        np.testing.assert_array_equal(aw, ac)
    assert abs(warm.cumulative_cost - cold.cumulative_cost) <= (
        1e-6 * cold.cumulative_cost)
    assert warm.cumulative_cost <= static.cumulative_cost * (1 + 1e-9)
    assert cold.cumulative_cost <= static.cumulative_cost * (1 + 1e-9)
    # training survived the churn: accuracy improved over the run
    assert warm.train.test_acc[-1] > warm.train.test_acc[0]


# -- streaming admission under capacities ------------------------------------

ADMIT_CHURN = dict(drift_m=60.0, move_frac=0.2, flip_frac=0.1,
                   depart_frac=0.25, arrive_frac=0.5)


class _FakeTrainer:
    """Just enough trainer surface for begin_round: the mask attribute and
    the arrival-readmit hook."""
    client_mask = None

    def __init__(self):
        self.readmits = []

    def readmit_clients(self, mask, assign, k):
        self.readmits.append(np.asarray(mask).copy())


def _capped(sc, caps):
    import dataclasses
    return dataclasses.replace(sc, max_devices=np.asarray(caps, np.int64))


def test_admission_queue_fills_then_drains_without_waking_solver(sc):
    """Arrivals beyond cap land in the overflow queue; the per-round O(K)
    admission tick drains them as churn (and re-solve rebalancing) frees
    headroom — and the admitted view NEVER exceeds a cap at any round."""
    caps = np.array([4, 4, 4])
    # exchange_samples=0: this test pins queue/drain mechanics, not escape
    # moves (satellite coverage for caps+exchanges lives in
    # test_scenario_churn), and the exchange-off solves keep it in the
    # fast tier
    runner = LiveHFELRunner(_capped(sc, caps), N, policy="incremental-warm",
                            resolve_every=2, churn=ADMIT_CHURN, seed=0,
                            exchange_samples=0)
    tr = _FakeTrainer()
    for rd in range(8):
        runner.begin_round(tr, rd)
        load = np.bincount(runner.assignment[runner.sc.active_mask],
                           minlength=K)
        assert (load <= caps).all(), f"cap exceeded at round {rd}: {load}"
        # queued devices are exactly the not-yet-admitted ones
        assert not runner.sc.active_mask[runner._queue].any()
    h = runner.history
    # sum(caps)=12 < 16 active: the initial admission must refuse some
    assert h.n_queued[0] > 0
    assert h.n_active[0] == N - h.n_queued[0]
    # the streaming path admitted queued devices as headroom appeared
    assert sum(h.n_admitted) > 0
    # readmitted arrivals reached the trainer hook
    assert len(tr.readmits) == sum(1 for a in h.n_admitted if a > 0)
    # nothing was dropped: the default overflow bound was never hit
    assert sum(h.n_rejected) == 0


def test_admission_overflow_bound_rejects_oldest(sc):
    """overflow_max=0 degenerates the queue to immediate rejection — every
    refused device is counted, none linger."""
    runner = LiveHFELRunner(_capped(sc, [4, 4, 4]), N, policy="static",
                            churn=ADMIT_CHURN, seed=0, overflow_max=0)
    tr = _FakeTrainer()
    runner.begin_round(tr, 0)
    runner.begin_round(tr, 1)
    h = runner.history
    assert h.n_queued == [0, 0]
    assert h.n_rejected[0] > 0
    with pytest.raises(ValueError, match="overflow_max"):
        LiveHFELRunner(sc, N, overflow_max=-1)


def test_uncapped_history_admission_fields_stay_zero(sc, ds):
    h = _live(sc, ds, "static", rounds=2, resolve_every=1)
    assert h.n_queued == [0, 0]
    assert h.n_admitted == [0, 0]
    assert h.n_rejected == [0, 0]
    d = h.as_dict()
    assert d["n_queued"] == [0, 0] and d["n_rejected"] == [0, 0]


def test_warm_cold_swap_parity_under_binding_caps(ds):
    """The PR-4 parity gate extends to capacitated scenarios: warm and cold
    must agree bit-for-bit at every swap point even while the admission
    queue churns the view between re-solves."""
    scc = make_large_scenario(N, K, seed=0, cap_slack=1.0)
    kw = dict(rounds=4, resolve_every=2, churn=ADMIT_CHURN, seed=0,
              local_iters=1, edge_iters=1)
    warm = run_live(scc, ds, policy="incremental-warm", verify=True, **kw)
    cold = run_live(scc, ds, policy="periodic-cold", **kw)
    assert warm.swap_rounds == cold.swap_rounds
    for r, aw, ac in zip(warm.swap_rounds, warm.swap_assignments,
                         cold.swap_assignments):
        np.testing.assert_array_equal(aw, ac,
                                      err_msg=f"diverged at round {r}")
    np.testing.assert_allclose(warm.system_cost, cold.system_cost, rtol=1e-6)
