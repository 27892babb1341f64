"""Live HFEL co-simulation: elastic edge re-association DURING federated
training.

The paper treats edge association and training as one system — the
association policy exists to cut the cost of the training rounds it
schedules — and this module finally runs them as one program: a
:class:`LiveHFELRunner` drives :class:`repro.fl.training.FederatedTrainer`
rounds while the :class:`repro.core.scenario.Scenario` churns underneath it.
Every global round

1. applies one seeded :func:`repro.core.scenario.perturb_scenario` tick
   (mobility drift, reach flips, arrivals/departures),
2. re-solves the edge association via a pluggable policy (below),
3. repairs the trainer's state for the churn — ``Scenario.active`` maps onto
   the trainer's ``client_mask`` through a
   :class:`repro.core.scenario.DeviceClientBridge`, departed devices are
   parked (masked out of aggregation but kept in the fixed-size arrays), and
   arrivals are re-admitted with their edge's CURRENT parameters
   (:meth:`FederatedTrainer.readmit_clients`),
4. hot-swaps the assignment between cloud aggregations (the swap point where
   the global weighted mean is invariant to the grouping — the property-test
   contract in ``tests/test_fl_training.py``), and
5. accumulates the paper's global system cost (eq. 17) for the round's
   assignment on the round's scenario, next to training accuracy.

Re-association policies
-----------------------
``static``
    The round-0 stable assignment is frozen; churn only ever triggers the
    minimal feasibility repair (:func:`repro.core.assoc_fast.repair_assignment`
    — departures park, unreachable devices fall to their nearest reachable
    server) with ZERO descent moves. The baseline the paper's premise says
    should lose under mobility.
``periodic-cold``
    Every ``resolve_every`` rounds, a FRESH engine is built on the churned
    scenario (full reach-map + toggle-cache rebuild) and descends from the
    repaired previous stable point.
``incremental-warm``
    Every ``resolve_every`` rounds, the round-0 engine's
    :meth:`~repro.core.assoc_fast.FastAssociationEngine.rerun_incremental`
    re-converges from the SAME repaired stable point, but with patched
    slot-index maps and a stale-row-only toggle-cache refresh.

Every timed solve (round-0, cold, warm) runs with ``finalize=False`` — the
non-verifying fast path returning just the assignment — so the association
timers are symmetric across policies: cost accounting happens exactly once
per round for every policy, on the shared reference-accuracy evaluator
(:func:`~repro.core.assoc_fast.assignment_true_cost`), OUTSIDE the
association timer.

Because ``periodic-cold`` descends from exactly the assignment
``incremental-warm`` repairs to (both via :func:`repair_assignment`, from
the same last-swap stable point and active mask), the PR-4 warm/cold parity
gate applies at EVERY swap point: the two policies must produce
bit-identical assignments round for round, while the warm policy spends
measurably less association wall time. ``run_live(verify=True)`` turns on
the engine-level parity assertion inside each warm re-solve as well.

Multi-tick deltas: when ``resolve_every > 1`` the scenario churns between
re-solves; the runner hands ``rerun_incremental`` the single combined
:func:`repro.core.scenario.diff_scenarios` delta between the last-swap
scenario and the current one, so one incremental re-solve absorbs any
number of ticks.

Streaming admission under capacities
------------------------------------
When the scenario carries per-edge caps (``Scenario.max_devices``), the
runner splits the world in two: the TRUE scenario keeps churning (its
``active`` mask says who *wants* to train), while the association stack only
ever sees the admitted *view* (``active`` = the admitted subset). Admission
is an O(K)-per-device greedy nearest-feasible placement
(:func:`repro.core.edge_association.greedy_admission`) that runs WITHOUT
waking the solver: arrivals land in a bounded FIFO overflow queue, an
admission tick drains it against current loads every round, and re-solve
rounds drain it again AFTER the global descent (the post-resolve drain) —
turning ``rerun_incremental`` from a batch-tick API into the periodic
global pass of an online service loop. A device the capacitated repair
cannot place (its reachable servers are all at cap) is demoted back to the
queue instead of crashing the round; when the queue overflows
``overflow_max``, the oldest entries are dropped and counted as rejected
(they re-enter only by departing and re-arriving in the true scenario).
Swap references are stored BEFORE the drain, so the warm/cold parity
contract above survives capacities: both policies descend from the same
pre-drain stable state. With no caps, none of this machinery is
instantiated and the historical behavior is untouched.
"""

from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from repro.core.assoc_fast import (DEFAULT_EXCHANGE_SAMPLES,
                                   FastAssociationEngine,
                                   assignment_true_cost, repair_assignment)
from repro.core.edge_association import (GroupSolver, NoFeasibleServerError,
                                         greedy_admission)
from repro.core.scenario import (DeviceClientBridge, Scenario,
                                 device_client_bridge, diff_scenarios,
                                 perturb_scenario)
from repro.data.federated import FederatedDataset
from repro.fl.training import TrainHistory, train_federated

POLICIES = ("static", "periodic-cold", "incremental-warm")

# one mild mobility tick per global round: 5% of devices drift, 2% lose a
# reach bit, 2% depart, 10% of the inactive pool returns — the operating
# regime of the churn benchmark (assoc_scale/churn), scaled to per-round use
DEFAULT_CHURN = {"drift_m": 60.0, "move_frac": 0.05, "flip_frac": 0.02,
                 "depart_frac": 0.02, "arrive_frac": 0.10}


def churn_tick(sc: Scenario, *, seed: int, r: int, churn: dict | None = None):
    """Round ``r``'s churn tick of a live run seeded with ``seed``: one
    :func:`perturb_scenario` step, returning ``(scenario, delta)``. Its seed
    depends on ``(seed, r)`` alone, so every policy faces the same scenario
    trajectory, and a caller can replay a run's scenarios round by round."""
    return perturb_scenario(sc, seed=(seed + 1) * 1_000_003 + r,
                            **(DEFAULT_CHURN if churn is None else churn))


@dataclass
class LiveHistory:
    """Per-round record of one live co-simulation.

    The round-indexed lists always have length ``rounds`` regardless of
    ``eval_every`` (training metrics live in ``train``, whose lists carry
    their own ``eval_rounds`` index). ``swap_rounds``/``swap_assignments``
    record every hot-swap, round 0's initial solve included."""

    policy: str
    resolve_every: int
    # -- round-indexed (length == rounds) --
    system_cost: list = field(default_factory=list)     # eq. (17)
    system_energy: list = field(default_factory=list)   # eq. (15)
    system_delay: list = field(default_factory=list)    # eq. (16)
    assoc_seconds: list = field(default_factory=list)
    swapped: list = field(default_factory=list)
    moves: list = field(default_factory=list)
    n_active: list = field(default_factory=list)
    n_arrived: list = field(default_factory=list)
    n_departed: list = field(default_factory=list)
    # -- streaming admission (all zero when the scenario has no caps) --
    n_queued: list = field(default_factory=list)     # queue depth at round end
    n_admitted: list = field(default_factory=list)   # streamed in this round
    n_rejected: list = field(default_factory=list)   # dropped from the queue
    # -- swap-indexed --
    swap_rounds: list = field(default_factory=list)
    swap_assignments: list = field(default_factory=list)
    train: TrainHistory | None = None

    @property
    def rounds(self) -> int:
        return len(self.system_cost)

    @property
    def cumulative_cost(self) -> float:
        """Sum of the per-round eq.-(17) costs — the figure of merit the
        re-association policies compete on."""
        return float(np.sum(self.system_cost))

    @property
    def assoc_seconds_total(self) -> float:
        return float(np.sum(self.assoc_seconds))

    def as_dict(self) -> dict:
        """JSON-friendly summary (per-swap assignments are kept only as
        counts; the arrays themselves stay on the object)."""
        return {
            "policy": self.policy, "resolve_every": self.resolve_every,
            "rounds": self.rounds,
            "system_cost": [float(c) for c in self.system_cost],
            "system_energy": [float(c) for c in self.system_energy],
            "system_delay": [float(c) for c in self.system_delay],
            "cumulative_cost": self.cumulative_cost,
            "assoc_seconds": [float(s) for s in self.assoc_seconds],
            "assoc_seconds_total": self.assoc_seconds_total,
            "swapped": [bool(s) for s in self.swapped],
            "moves": [int(m) for m in self.moves],
            "n_active": [int(a) for a in self.n_active],
            "n_arrived": [int(a) for a in self.n_arrived],
            "n_departed": [int(d) for d in self.n_departed],
            "n_queued": [int(q) for q in self.n_queued],
            "n_admitted": [int(a) for a in self.n_admitted],
            "n_rejected": [int(x) for x in self.n_rejected],
            "swap_rounds": [int(r) for r in self.swap_rounds],
            "train": self.train.as_dict() if self.train is not None else None,
        }


class LiveHFELRunner:
    """The round policy object behind :func:`run_live` — usable directly as
    ``train_federated(..., round_hook=runner)``.

    ``begin_round(trainer, r)`` performs the full churn/re-associate/repair
    step described in the module docstring and returns the round's
    (n_clients,) assignment. State between rounds: the current scenario,
    the device-axis assignment, and (for ``incremental-warm``) the live
    association engine with its toggle-cache warm state.
    """

    def __init__(self, sc: Scenario, n_clients: int, *,
                 policy: str = "incremental-warm", resolve_every: int = 1,
                 churn: dict | None = None, seed: int = 0,
                 kind: str = "fast", profile: str = "coarse",
                 rel_tol: float = 1e-3, compact: bool | str = "auto",
                 shards: int | None = None, ra_backend: str = "xla",
                 max_moves: int = 10_000,
                 exchange_samples: int = DEFAULT_EXCHANGE_SAMPLES,
                 verify: bool = False, overflow_max: int = 64,
                 bridge: DeviceClientBridge | None = None):
        if policy not in POLICIES:
            raise ValueError(f"policy must be one of {POLICIES}, got {policy!r}")
        if resolve_every < 1:
            raise ValueError("resolve_every must be >= 1")
        if overflow_max < 0:
            raise ValueError("overflow_max must be >= 0")
        # -- streaming admission state (only instantiated under caps): the
        # TRUE scenario churns; the association stack sees the admitted view
        self.sc = sc
        self._sc_full = sc
        self._cap = sc.capacity
        self.overflow_max = overflow_max
        self._queue: list[int] = []
        self._round_rejected = 0
        self._admitted: np.ndarray | None = None
        if self._cap is not None:
            admitted = sc.active_mask.copy()
            act = np.flatnonzero(admitted)
            load = np.zeros(sc.n_servers, dtype=np.int64)
            placed = greedy_admission(sc.dist, sc.eff_avail, load,
                                      self._cap, act)
            refused = act[placed < 0]
            admitted[refused] = False
            self._admitted = admitted
            self._queue = refused.tolist()
            self._round_rejected = self._trim_queue()
            self.sc = dataclasses.replace(sc, active=admitted.copy())
        self.policy = policy
        self.resolve_every = resolve_every
        self.churn = dict(DEFAULT_CHURN if churn is None else churn)
        self.seed = seed
        self.kind = kind
        self.profile = profile
        self.rel_tol = rel_tol
        self.compact = compact
        self.shards = shards
        self.ra_backend = ra_backend
        self.max_moves = max_moves
        self.exchange_samples = exchange_samples
        self.verify = verify
        self.bridge = bridge or device_client_bridge(sc, n_clients)
        if self.bridge.n_devices != sc.n_devices:
            raise ValueError("bridge does not match the scenario's device axis")
        if self.bridge.n_clients != n_clients:
            raise ValueError(
                f"bridge maps {self.bridge.n_clients} clients but the "
                f"dataset has {n_clients}")
        # reference-accuracy cost evaluator, shared by every policy and kept
        # OUT of the association timer; valid across churn because device/
        # server physical params are perturbation-invariant ("proportional"
        # reads distances, so it rebuilds per round)
        self._eval_solver = (None if kind == "proportional" else
                             GroupSolver(sc, kind, seed=seed,
                                         profile="default"))
        self.engine: FastAssociationEngine | None = None
        self.assignment: np.ndarray | None = None   # device axis, parked incl.
        # all association-side round state tracks the VIEW (self.sc), which
        # equals the true scenario whenever there are no caps
        self._active_prev = self.sc.active_mask.copy()
        self._sc_at_swap = self.sc
        self._active_at_swap = self.sc.active_mask.copy()
        self._assign_at_swap: np.ndarray | None = None
        self.history = LiveHistory(policy=policy, resolve_every=resolve_every)

    # -- internals -----------------------------------------------------------

    def _new_engine(self, sc: Scenario) -> FastAssociationEngine:
        return FastAssociationEngine(sc, kind=self.kind, seed=self.seed,
                                     rel_tol=self.rel_tol,
                                     profile=self.profile,
                                     compact=self.compact,
                                     shards=self.shards,
                                     ra_backend=self.ra_backend)

    # -- streaming admission (capacitated scenarios only) --------------------

    def _rebuild_view(self) -> None:
        self.sc = dataclasses.replace(self._sc_full,
                                      active=self._admitted.copy())

    def _trim_queue(self) -> int:
        """Bound the overflow queue: drop the OLDEST entries beyond
        ``overflow_max`` (they starved longest and their demand is stalest;
        they re-enter only by departing and re-arriving in the true
        scenario). Returns the number dropped."""
        drop = len(self._queue) - self.overflow_max
        if drop > 0:
            self._queue = self._queue[drop:]
        return max(drop, 0)

    def _admission_tick(self) -> int:
        """Drain the overflow queue greedily against CURRENT loads — the
        O(K)-per-device streaming admission path; no solver involvement.
        Admitted devices enter the view and take their placement directly
        in ``self.assignment``; the rest stay queued in FIFO order."""
        if not self._queue:
            return 0
        k = self._sc_full.n_servers
        load = np.bincount(self.assignment[self._admitted], minlength=k)
        devices = np.asarray(self._queue, dtype=np.int64)
        placed = greedy_admission(self._sc_full.dist, self._sc_full.eff_avail,
                                  load, self._cap, devices)
        got = placed >= 0
        if got.any():
            self.assignment[devices[got]] = placed[got]
            self._admitted[devices[got]] = True
            self._queue = devices[~got].tolist()
            self._rebuild_view()
        return int(got.sum())

    def _repair_with_demotions(self, prev_assign: np.ndarray,
                               old_active: np.ndarray) -> np.ndarray:
        """Capacitated host repair with overflow demotion: a device
        :func:`repair_assignment` cannot place (every reachable server at
        cap) is demoted from the admitted view into the queue and the
        repair re-runs on the shrunk view. Pre-validating here — BEFORE
        any engine call — matters because the engine mutates its reach
        maps before repairing; by the time its internal (deterministic,
        input-identical) repair runs, this loop has guaranteed it
        succeeds. Terminates: every retry strictly shrinks the admitted
        set. Leaves ``self.sc`` as the final view."""
        while True:
            self._rebuild_view()
            try:
                assign, *_ = repair_assignment(self.sc, prev_assign,
                                               old_active)
                return assign
            except NoFeasibleServerError as e:
                self._admitted[e.devices] = False
                self._queue.extend(int(d) for d in e.devices)

    def _record(self, *, assoc_s: float, swapped: bool, moves: int,
                arrived: int, departed: int, admitted: int = 0) -> None:
        h = self.history
        # _eval_solver is None for "proportional" (distance-dependent):
        # assignment_true_cost then builds a fresh per-round solver itself
        e, t, c = assignment_true_cost(self.sc, self.assignment,
                                       solver=self._eval_solver,
                                       kind=self.kind, seed=self.seed)
        h.system_cost.append(c)
        h.system_energy.append(e)
        h.system_delay.append(t)
        h.assoc_seconds.append(assoc_s)
        h.swapped.append(swapped)
        h.moves.append(moves)
        h.n_active.append(int(self.sc.active_mask.sum()))
        h.n_arrived.append(arrived)
        h.n_departed.append(departed)
        h.n_queued.append(len(self._queue))
        h.n_admitted.append(admitted)
        h.n_rejected.append(self._round_rejected)
        self._round_rejected = 0
        if swapped:
            h.swap_rounds.append(len(h.system_cost) - 1)
            h.swap_assignments.append(self.assignment.copy())

    # -- the round policy ----------------------------------------------------

    def begin_round(self, trainer, r: int):
        if r == 0:
            trainer.client_mask = jnp.asarray(
                self.bridge.client_mask(self.sc.active_mask))
            t0 = time.perf_counter()
            self.engine = self._new_engine(self.sc)
            assignment = self.engine.run(
                "nearest", max_moves=self.max_moves,
                exchange_samples=self.exchange_samples, finalize=False)
            assoc_s = time.perf_counter() - t0
            self.assignment = np.asarray(assignment)
            self._assign_at_swap = self.assignment.copy()
            self._record(assoc_s=assoc_s, swapped=True,
                         moves=self.engine.last_moves, arrived=0, departed=0)
            if self.policy != "incremental-warm":
                # only the warm policy re-enters the engine (toggle caches,
                # reach maps, device buffers) after round 0 — don't keep
                # that state resident for the whole run under the others
                self.engine = None
            return self.bridge.client_assignment(self.assignment)

        capped = self._admitted is not None
        if capped:
            admitted_before = self._admitted.copy()
            self._sc_full, delta = churn_tick(self._sc_full, seed=self.seed,
                                              r=r, churn=self.churn)
            full_active = self._sc_full.active_mask
            # true-scenario departures leave the admitted set and the queue;
            # arrivals join the queue — streaming admission is the ONLY path
            # into the training population under caps
            self._admitted &= full_active
            self._queue = [d for d in self._queue if full_active[d]]
            self._queue.extend(np.flatnonzero(delta.arrived).tolist())
            self._rebuild_view()
        else:
            self.sc, delta = churn_tick(self.sc, seed=self.seed, r=r,
                                        churn=self.churn)
        assoc_s, moves, swapped, admitted_n = 0.0, 0, False, 0
        resolve = self.policy != "static" and r % self.resolve_every == 0
        if resolve and self.policy == "incremental-warm":
            # the delta derivation is part of the warm path's per-swap work,
            # so it belongs inside the association timer (cold's timer
            # likewise spans its repair + engine build)
            t0 = time.perf_counter()
            if capped:
                # pre-validate the engine's repair inputs: demote devices
                # the capacitated repair cannot place, so the engine's own
                # (deterministic, input-identical) repair cannot raise
                self._repair_with_demotions(self.engine.stable_assignment,
                                            self._active_at_swap)
            combined = diff_scenarios(self._sc_at_swap, self.sc)
            self.assignment = self.engine.rerun_incremental(
                self.sc, combined, max_moves=self.max_moves,
                exchange_samples=self.exchange_samples, verify=self.verify,
                finalize=False)
            assoc_s = time.perf_counter() - t0
            moves, swapped = self.engine.last_moves, True
        elif resolve:   # periodic-cold
            t0 = time.perf_counter()
            if capped:
                assign0 = self._repair_with_demotions(self._assign_at_swap,
                                                      self._active_at_swap)
            else:
                assign0, *_ = repair_assignment(self.sc, self._assign_at_swap,
                                                self._active_at_swap)
            cold = self._new_engine(self.sc)
            assignment = cold.run(assignment=assign0,
                                  max_moves=self.max_moves,
                                  exchange_samples=self.exchange_samples,
                                  finalize=False)
            assoc_s = time.perf_counter() - t0
            self.assignment = np.asarray(assignment)
            moves, swapped = cold.last_moves, True
        else:
            # static policy, and the off-cycle rounds of the re-association
            # policies: minimal feasibility repair, zero descent moves
            if capped:
                self.assignment = self._repair_with_demotions(
                    self.assignment, self._active_prev)
            else:
                self.assignment, *_ = repair_assignment(
                    self.sc, self.assignment, self._active_prev)
        if swapped:
            # swap refs are stored PRE-drain: the next warm re-solve diffs
            # against (and the next cold rebuild repairs from) exactly the
            # state the engines converged on, which is what keeps warm/cold
            # parity bit-identical under capacities
            self._sc_at_swap = self.sc
            self._active_at_swap = self.sc.active_mask.copy()
            self._assign_at_swap = self.assignment.copy()
        if capped:
            # admission tick every round; on swap rounds this is the
            # post-resolve drain (stable loads just freed by the descent)
            admitted_n = self._admission_tick()
            self._round_rejected += self._trim_queue()
        active = self.sc.active_mask
        self._active_prev = active.copy()

        trainer.client_mask = jnp.asarray(self.bridge.client_mask(active))
        newly = (self._admitted & ~admitted_before if capped
                 else delta.arrived)
        arrivals_c = self.bridge.client_mask(newly)
        if arrivals_c.any():
            trainer.readmit_clients(
                jnp.asarray(arrivals_c),
                jnp.asarray(self.bridge.client_assignment(self.assignment)),
                self.sc.n_servers)
        self._record(assoc_s=assoc_s, swapped=swapped, moves=moves,
                     arrived=int(delta.arrived.sum()),
                     departed=int(delta.departed.sum()),
                     admitted=admitted_n)
        return self.bridge.client_assignment(self.assignment)


def run_live(sc: Scenario, ds: FederatedDataset, *,
             policy: str = "incremental-warm", rounds: int = 10,
             resolve_every: int = 1, churn: dict | None = None, seed: int = 0,
             local_iters: int = 5, edge_iters: int = 2, lr: float = 0.05,
             model: str = "mlr", eval_every: int = 1, train_seed: int = 0,
             kind: str = "fast", profile: str = "coarse",
             rel_tol: float = 1e-3, compact: bool | str = "auto",
             shards: int | None = None, ra_backend: str = "xla",
             max_moves: int = 10_000,
             exchange_samples: int = DEFAULT_EXCHANGE_SAMPLES,
             verify: bool = False, overflow_max: int = 64,
             bridge: DeviceClientBridge | None = None) -> LiveHistory:
    """Run one live HFEL co-simulation end-to-end; returns its
    :class:`LiveHistory` (training metrics under ``.train``).

    The association side (``policy``/``resolve_every``/engine knobs) and the
    training side (``local_iters``/``edge_iters``/``lr``/``model``) share
    the scenario through a :func:`device_client_bridge`; churn ticks are
    seeded from ``seed`` and round index only, so different policies at the
    same ``seed`` face the exact same scenario trajectory — the controlled
    comparison the live benchmark and the parity tests rely on.

    ``shards=p`` / ``ra_backend="pallas"`` reach every engine the policies
    build (round-0, periodic-cold rebuilds, the warm engine), so the live
    loop can run the PR-6 sharded sweep; the sharded path keeps the
    bit-identical-assignment contract, hence identical histories.

    ``exchange_samples`` defaults to
    :data:`repro.core.assoc_fast.DEFAULT_EXCHANGE_SAMPLES` (= 64), the SAME
    default as ``FastAssociationEngine.run`` — live runs no longer silently
    drop the Definition-5 escape moves — and is legal under ``shards=p``
    (the sampled-exchange pass is distributed with a bit-identical winner
    merge). Warm/cold swap parity holds with exchanges on: both policies
    descend from the same repaired assignment with the same
    ``PRNGKey(seed)`` stream. Pass 0 for transfer-only descent.

    On a capacitated scenario (``sc.max_devices`` set), arrivals the edges
    cannot admit wait in a FIFO queue bounded by ``overflow_max`` (see
    "Streaming admission under capacities" in the module docstring); the
    per-round queue/admission/rejection counts land in the history's
    ``n_queued`` / ``n_admitted`` / ``n_rejected``.
    """
    runner = LiveHFELRunner(sc, ds.n_clients, policy=policy,
                            resolve_every=resolve_every, churn=churn,
                            seed=seed, kind=kind, profile=profile,
                            rel_tol=rel_tol, compact=compact,
                            shards=shards, ra_backend=ra_backend,
                            max_moves=max_moves,
                            exchange_samples=exchange_samples, verify=verify,
                            overflow_max=overflow_max, bridge=bridge)
    hist = train_federated(ds, method="hfel", n_servers=sc.n_servers,
                           local_iters=local_iters, edge_iters=edge_iters,
                           rounds=rounds, lr=lr, model=model, seed=train_seed,
                           eval_every=eval_every, round_hook=runner)
    runner.history.train = hist
    return runner.history
