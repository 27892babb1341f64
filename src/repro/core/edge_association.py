"""Edge association across multiple edge servers — paper Section IV.

Implements Algorithm 3 (device *transferring* and *exchanging* adjustments
iterated to a stable system point, Defs. 4-6 / Thm. 3) plus a beyond-paper
batched variant that evaluates every candidate transfer of a round in one
vmapped solve and applies the steepest permitted move.

Permission rules
----------------
The paper's Definition 3 ("pareto order") literally requires every changed
group's utility not to decrease — but moving a device INTO a group always
adds cost to it (every added device contributes a positive a_n/beta term),
so under the strict reading no transfer is ever permitted, contradicting the
paper's own Figs. 3-6.  We therefore implement both readings:

* ``permission="utilitarian"`` (default, matches the paper's observed
  behaviour and its global objective (17)): an adjustment is permitted iff
  the system-wide cost strictly decreases.
* ``permission="pareto"`` (strict Definition 3): additionally no involved
  server's cost may increase.

Global surrogate objective
--------------------------
Following the paper's decomposition v(DS) = sum_i v(S_i), the association
optimizes  sum_i [ C_i + 1{S_i != {}} * (lambda_e E^cloud_i +
lambda_t T^cloud_i) ]  — the sum-of-servers surrogate of (17) (the true
delay term is a max over servers; both are reported).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import resource_allocation as ra
from repro.core.cost_model import (DeviceParams, LearningParams, RAConstants,
                                   ServerParams, cloud_delay, cloud_energy,
                                   global_cost, ra_constants)
from repro.core.scenario import Scenario


# ---------------------------------------------------------------------------
# Batched per-server group solver with pluggable schemes
# ---------------------------------------------------------------------------

SCHEME_KINDS = ("optimal", "fast", "paper", "comp_only", "comm_only",
                "uniform", "proportional")


def _fixed_eval(c: RAConstants, mask, beta, random_f) -> ra.RASolution:
    """Evaluate (18) at a fixed (random-f, given-beta) point — no optimization."""
    from repro.core.cost_model import ra_objective
    f = jnp.clip(random_f, c.f_min, c.f_max)
    safe_beta = jnp.where(mask, jnp.maximum(beta, 1e-12), 1.0)
    cost = jnp.where(jnp.any(mask), ra_objective(c, mask, f, safe_beta), 0.0)
    deadline = jnp.max(jnp.where(mask, c.d / safe_beta + c.e / f, 0.0))
    return ra.RASolution(f=f, beta=jnp.where(mask, beta, 0.0),
                         cost=cost, deadline=deadline)


def solve_group(kind: str, c: RAConstants, mask, *, random_f=None,
                inv_dist_row=None, profile: str = "default") -> ra.RASolution:
    """Pure single-group RA dispatch shared by :class:`GroupSolver` and the
    device-resident engine in :mod:`repro.core.assoc_fast`.

    ``c`` holds ONE server's constants; ``mask`` selects the group members.
    ``random_f`` / ``inv_dist_row`` supply the fixed decisions the degenerate
    §V.A schemes need; ``profile`` picks a :data:`ra.SCREEN_PROFILES` preset
    for the ``fast`` kind (the others are profile-free).
    """
    n_active = jnp.maximum(jnp.sum(mask), 1)
    if kind == "fast":
        return ra.solve_fixed_point(c, mask, **ra.SCREEN_PROFILES[profile])
    if kind in ("optimal", "paper"):
        fn = {"optimal": ra.solve_exact, "paper": ra.solve_paper}[kind]
        return fn(c, mask)
    if kind == "comp_only":
        beta = jnp.where(mask, 1.0 / n_active, 0.0)
        return ra.optimize_f_given_beta(c, mask, beta)
    if kind == "comm_only":
        return ra.optimize_beta_given_f(c, mask, random_f)
    if kind == "uniform":
        beta = jnp.where(mask, 1.0 / n_active, 0.0)
        return _fixed_eval(c, mask, beta, random_f)
    if kind == "proportional":
        score = jnp.where(mask, inv_dist_row, 0.0)
        beta = score / jnp.maximum(jnp.sum(score), 1e-12)
        return _fixed_eval(c, mask, beta, random_f)
    raise ValueError(kind)


@partial(jax.jit, static_argnames=("kind", "profile"))
# hfellint: disable=HFEL006 -- consts/inv_dist are cache-resident constants
def _solve_batch_pure(consts, random_f, inv_dist, server_ids, masks, *,
                      kind, profile):
    """Module-level vmapped group solve so the jit cache is shared across
    every GroupSolver instance (per-instance jits used to recompile each
    bucket size for each new engine)."""

    def one(s, m):
        c = jax.tree.map(lambda x: x[s], consts)
        return solve_group(kind, c, m, random_f=random_f,
                           inv_dist_row=inv_dist[s], profile=profile)

    return jax.vmap(one)(server_ids, masks)


class GroupSolver:
    """Caches per-server RA constants and solves (server, member-mask) groups.

    ``kind`` selects the resource-allocation scheme of §V.A:
      optimal      — solve_exact            (full joint optimization)
      fast         — solve_fixed_point      (KKT solve, ~1k steps deep)
      paper        — solve_paper            (Algorithm 2 faithful)
      comp_only    — optimal f, uniform beta
      comm_only    — optimal beta, random fixed f
      uniform      — uniform beta, random fixed f
      proportional — beta inversely proportional to distance, random fixed f
    """

    def __init__(self, sc: Scenario, kind: str = "fast", *, seed: int = 0,
                 profile: str = "default"):
        assert kind in SCHEME_KINDS, kind
        assert profile in ra.SCREEN_PROFILES, profile
        self.sc = sc
        self.kind = kind
        self.profile = profile
        n, k = sc.n_devices, sc.n_servers
        # batched constants: leading axis = server
        self.consts = jax.vmap(
            lambda bw, n0: ra_constants(sc.dev, bw, n0, sc.lp)
        )(sc.srv.bandwidth, sc.srv.noise)
        rng = np.random.default_rng(seed)
        fmin = np.asarray(sc.dev.f_min)
        fmax = np.asarray(sc.dev.f_max)
        self.random_f = jnp.asarray(
            rng.uniform(fmin, fmax).astype(np.float32))
        # inverse-distance scores per (server, device) for "proportional"
        inv = 1.0 / np.maximum(np.asarray(sc.dist), 1.0)
        self.inv_dist = jnp.asarray(inv.astype(np.float32))

    def with_profile(self, profile: str) -> "GroupSolver":
        """A view of this solver at another iteration profile; the batched
        constants and fixed random draws are shared, not recomputed."""
        assert profile in ra.SCREEN_PROFILES, profile
        if profile == self.profile:
            return self
        clone = object.__new__(GroupSolver)
        clone.__dict__.update(self.__dict__)
        clone.profile = profile
        return clone

    def _consts_at(self, i) -> RAConstants:
        return jax.tree.map(lambda x: x[i], self.consts)

    def _solve_one(self, server_idx, mask):
        return solve_group(self.kind, self._consts_at(server_idx), mask,
                           random_f=self.random_f,
                           inv_dist_row=self.inv_dist[server_idx],
                           profile=self.profile)

    def _batch_fn(self, server_ids, masks):
        return _solve_batch_pure(self.consts, self.random_f, self.inv_dist,
                                 server_ids, masks, kind=self.kind,
                                 profile=self.profile)

    def solve_batch(self, server_ids: jnp.ndarray, masks: jnp.ndarray) -> ra.RASolution:
        """Solve C candidate groups at once: server_ids (C,), masks (C, N).

        The batch is split into power-of-two chunks (binary decomposition of
        C) so the vmapped solver still compiles once per bucket size, but no
        all-zero padding rows burn full RA iterations — the old next-pow2
        padding wasted up to 2x solves on odd batch sizes.
        """
        server_ids = np.asarray(server_ids)
        masks = np.asarray(masks)
        c = server_ids.shape[0]
        if c == 0:
            sol = self._batch_fn(jnp.zeros(1, np.int64),
                                 jnp.zeros((1, masks.shape[1]), bool))
            return jax.tree.map(lambda x: x[:0], sol)
        chunks = []
        off = 0
        while off < c:
            size = 1 << ((c - off).bit_length() - 1)   # largest pow2 <= rest
            chunks.append(self._batch_fn(
                jnp.asarray(server_ids[off:off + size]),
                jnp.asarray(masks[off:off + size])))
            off += size
        if len(chunks) == 1:
            return chunks[0]
        return jax.tree.map(lambda *xs: jnp.concatenate(xs), *chunks)


# ---------------------------------------------------------------------------
# Guarded feasibility helpers (shared by host + device engines + live loop)
# ---------------------------------------------------------------------------

class NoFeasibleServerError(RuntimeError):
    """Raised when a device has no reachable (and, under capacities, no
    admitting) server — the replacement for the old silent all-``inf``
    ``argmin``, which parked such devices on server 0 with no signal.

    ``devices`` lists the offending device indices so callers (e.g. the
    live admission loop) can demote or queue them instead of crashing.
    """

    def __init__(self, devices, reason: str = "no feasible server"):
        self.devices = np.atleast_1d(np.asarray(devices, dtype=np.int64))
        super().__init__(f"{reason} for device(s) {self.devices.tolist()}")


def nearest_feasible(dist: np.ndarray, feasible: np.ndarray, *,
                     need: np.ndarray | None = None) -> np.ndarray:
    """Nearest feasible server per device, with the zero-feasible case made
    EXPLICIT instead of numpy's silent ``argmin(all-inf column) == 0``.

    ``dist``/``feasible`` are (K, N); returns (N,) int64. Devices outside
    the ``need`` mask (default: all devices need a slot) are exempt from
    the check — callers overwrite those slots — while a needed device with
    an empty feasible column raises :class:`NoFeasibleServerError`.
    """
    feasible = np.asarray(feasible, dtype=bool)
    any_ok = feasible.any(axis=0)
    satisfied = any_ok if need is None else any_ok | ~np.asarray(need, bool)
    if not satisfied.all():
        raise NoFeasibleServerError(np.flatnonzero(~satisfied))
    return np.argmin(np.where(feasible, np.asarray(dist), np.inf), axis=0)


def parked_slots(sc: Scenario) -> np.ndarray:
    """Deterministic bookkeeping slot per device: nearest raw-reachable
    server, falling back EXPLICITLY to the globally nearest server on a
    zero-raw-reach column (possible only on hand-built scenarios — the
    generators repair raw reach for every device). Parked slots carry no
    cost and belong to no group; they only keep assignment arrays
    fixed-size, so reach there is a nicety, not a constraint.
    """
    dist = np.asarray(sc.dist)
    raw = np.asarray(sc.avail, dtype=bool)
    slots = np.argmin(np.where(raw, dist, np.inf), axis=0)
    orphan = ~raw.any(axis=0)
    if orphan.any():
        slots[orphan] = np.argmin(dist[:, orphan], axis=0)
    return slots


def greedy_admission(dist: np.ndarray, feasible: np.ndarray,
                     load: np.ndarray, cap: np.ndarray,
                     devices: np.ndarray) -> np.ndarray:
    """Sequential nearest-feasible placement under per-edge caps.

    Walks ``devices`` in the given order; each takes the nearest server
    among ``feasible[:, d] & (load < cap)`` and bumps that server's
    ``load`` (mutated in place). Returns placements aligned with
    ``devices``, ``-1`` marking devices NO server could admit — the caller
    decides whether that is an error (solver init/repair) or an
    overflow-queue entry (the live admission loop). O(K) vectorized per
    device with no solver involvement: this IS the streaming admission
    primitive.
    """
    dist = np.asarray(dist)
    feasible = np.asarray(feasible, dtype=bool)
    devices = np.asarray(devices, dtype=np.int64)
    out = np.full(devices.shape[0], -1, dtype=np.int64)
    for r, d in enumerate(devices):
        cand = feasible[:, d] & (load < cap)
        if not cand.any():
            continue
        j = int(np.argmin(np.where(cand, dist[:, d], np.inf)))
        out[r] = j
        load[j] += 1
    return out


# ---------------------------------------------------------------------------
# Association state and result
# ---------------------------------------------------------------------------

def initial_assignment(sc: Scenario, avail: np.ndarray, rng,
                       init: str = "nearest") -> np.ndarray:
    """Initial association (§II.C / Algorithm 3 line 2), shared by the host
    and device engines so 'random' inits stay draw-for-draw identical.

    On churn scenarios (``sc.active`` set) only active devices draw a real
    placement from ``avail`` (normally the *effective* availability);
    inactive devices get a deterministic parked slot (:func:`parked_slots`)
    that exists purely so the assignment array stays fixed-size (they
    belong to no group and cost nothing). An active device with an empty
    ``avail`` column raises :class:`NoFeasibleServerError` instead of the
    old silent server-0 fallback. With ``sc.capacity`` set, 'nearest'
    becomes greedy sequential admission in device order and 'random' draws
    restrict to servers with headroom at the device's turn (draw-for-draw
    identical to the uncapacitated path whenever caps never bind).
    """
    active = sc.active_mask
    cap = sc.capacity
    avail = np.asarray(avail, dtype=bool)
    out = np.empty(sc.n_devices, dtype=np.int64)
    out[~active] = parked_slots(sc)[~active]
    act = np.flatnonzero(active)
    if init == "nearest":
        if cap is None:
            out[active] = nearest_feasible(sc.dist, avail,
                                           need=active)[active]
            return out
        load = np.zeros(sc.n_servers, dtype=np.int64)
        placed = greedy_admission(sc.dist, avail, load, cap, act)
        if (placed < 0).any():
            raise NoFeasibleServerError(act[placed < 0],
                                        "no admitting server")
        out[act] = placed
        return out
    if init == "random":
        load = np.zeros(sc.n_servers, dtype=np.int64)
        for d in act:
            ok = avail[:, d] if cap is None else avail[:, d] & (load < cap)
            choices = np.flatnonzero(ok)
            if choices.size == 0:
                raise NoFeasibleServerError(
                    [d], "no feasible server" if cap is None
                    else "no admitting server")
            out[d] = rng.choice(choices)
            load[out[d]] += 1
        return out
    raise ValueError(init)


@dataclass
class AssociationResult:
    assignment: np.ndarray            # (N,) device -> server
    f: np.ndarray                     # (N,)
    beta: np.ndarray                  # (N,)
    server_cost: np.ndarray           # (K,) C_i at the stable point
    total_cost: float                 # surrogate objective (see module doc)
    true_energy: float                # eq. (15)
    true_delay: float                 # eq. (16)
    true_cost: float                  # eq. (17)
    n_adjustments: int                # applied permitted adjustments (Figs 5-6)
    n_rounds: int
    cost_trace: list = field(default_factory=list)


class AssociationEngine:
    """Runs initialization + adjustment iterations to a stable system point."""

    def __init__(self, sc: Scenario, *, kind: str = "fast",
                 permission: str = "utilitarian", min_residual_group: int = 2,
                 seed: int = 0, rel_tol: float = 1e-5):
        self.sc = sc
        self.solver = GroupSolver(sc, kind, seed=seed)
        self.permission = permission
        self.min_residual = min_residual_group
        self.rel_tol = rel_tol
        self.rng = np.random.default_rng(seed)
        self._cache: dict[tuple[int, frozenset], float] = {}
        # effective availability: on churn scenarios inactive devices can
        # associate with no one, so they never become transfer/exchange
        # candidates (and _groups_of keeps them out of every group)
        self.avail = np.asarray(sc.eff_avail)                 # (K, N)
        self._active = sc.active_mask
        # per-edge admission caps: a server at cap rejects inbound transfers
        # (exchanges are 1-for-1, hence cap-neutral and never gated)
        self.cap = sc.capacity
        self.cloud_const = np.asarray(
            sc.lp.lambda_e * cloud_energy(sc.srv)
            + sc.lp.lambda_t * cloud_delay(sc.srv), dtype=np.float64)

    # -- group cost with memoization (the paper's history sets h_i) ---------

    def group_cost(self, server: int, members: frozenset) -> float:
        key = (server, members)
        if key not in self._cache:
            mask = np.zeros(self.sc.n_devices, bool)
            mask[list(members)] = True
            sol = self.solver.solve_batch(np.array([server]), mask[None, :])
            base = float(np.asarray(sol.cost)[0])
            self._cache[key] = base + (self.cloud_const[server] if members else 0.0)
        return self._cache[key]

    def group_costs_batch(self, pairs: list[tuple[int, frozenset]]) -> np.ndarray:
        """Memoized batched evaluation of many (server, members) groups."""
        missing = [p for p in set(pairs) if p not in self._cache]
        if missing:
            servers = np.array([s for s, _ in missing])
            masks = np.zeros((len(missing), self.sc.n_devices), bool)
            for r, (_, mem) in enumerate(missing):
                masks[r, list(mem)] = True
            sols = self.solver.solve_batch(servers, masks)
            costs = np.asarray(sols.cost, dtype=np.float64)
            for p, c in zip(missing, costs):
                self._cache[p] = float(c) + (self.cloud_const[p[0]] if p[1] else 0.0)
        return np.array([self._cache[p] for p in pairs])

    # -- initial association (§II.C / Algorithm 3 line 2) -------------------

    def initial_assignment(self, init: str = "nearest") -> np.ndarray:
        return initial_assignment(self.sc, self.avail, self.rng, init)

    def _check_caps(self, groups) -> None:
        """Explicit assignments must enter the descent cap-feasible; the
        move rules then keep them so (transfers are gated, exchanges are
        cap-neutral)."""
        if self.cap is None:
            return
        over = [i for i, g in enumerate(groups) if len(g) > self.cap[i]]
        if over:
            raise ValueError(
                f"assignment exceeds max_devices at server(s) {over}")

    # -- permission test -----------------------------------------------------

    def _permitted(self, old_costs: list[float], new_costs: list[float]) -> bool:
        scale = max(sum(old_costs), 1e-9)
        improves = sum(new_costs) < sum(old_costs) - self.rel_tol * scale
        if self.permission == "utilitarian":
            return improves
        no_harm = all(nc <= oc + self.rel_tol * max(oc, 1e-9)
                      for oc, nc in zip(old_costs, new_costs))
        return improves and no_harm

    # -- faithful Algorithm 3 ------------------------------------------------

    def run(self, init: str = "nearest", *, max_rounds: int = 200,
            exchange_samples: int = 1,
            assignment: np.ndarray | None = None) -> AssociationResult:
        assignment = (self.initial_assignment(init) if assignment is None
                      else np.asarray(assignment).copy())
        groups = self._groups_of(assignment)
        self._check_caps(groups)
        n, k = self.sc.n_devices, self.sc.n_servers
        n_adj = 0
        trace = [self._total(groups)]

        for rnd in range(max_rounds):
            changed = False
            # line 8-10: every device tries every permitted transfer
            for dev in range(n):
                src = int(assignment[dev])
                if len(groups[src]) <= self.min_residual:
                    continue
                targets = [j for j in range(k)
                           if j != src and self.avail[j, dev]
                           and (self.cap is None
                                or len(groups[j]) < self.cap[j])]
                if not targets:
                    continue
                src_after = groups[src] - {dev}
                pairs = [(src, groups[src]), (src, src_after)]
                for j in targets:
                    pairs += [(j, groups[j]), (j, groups[j] | {dev})]
                self.group_costs_batch(pairs)     # warm the cache in one shot
                best = None
                for j in targets:
                    old = [self.group_cost(src, groups[src]),
                           self.group_cost(j, groups[j])]
                    new = [self.group_cost(src, src_after),
                           self.group_cost(j, groups[j] | {dev})]
                    if self._permitted(old, new):
                        delta = sum(new) - sum(old)
                        if best is None or delta < best[0]:
                            best = (delta, j)
                if best is not None:
                    j = best[1]
                    groups[src] = src_after
                    groups[j] = groups[j] | {dev}
                    assignment[dev] = j
                    n_adj += 1
                    changed = True
                    trace.append(self._total(groups))
            # line 11: random exchange attempts
            for _ in range(exchange_samples):
                if self._try_exchange(assignment, groups):
                    n_adj += 1
                    changed = True
                    trace.append(self._total(groups))
            if not changed:
                return self._finalize(assignment, groups, n_adj, rnd + 1, trace)
        return self._finalize(assignment, groups, n_adj, max_rounds, trace)

    def _try_exchange(self, assignment, groups) -> bool:
        k = self.sc.n_servers
        occupied = [i for i in range(k) if groups[i]]
        if len(occupied) < 2:
            return False
        i, j = self.rng.choice(occupied, size=2, replace=False)
        dev_n = int(self.rng.choice(sorted(groups[i])))
        dev_m = int(self.rng.choice(sorted(groups[j])))
        if not (self.avail[j, dev_n] and self.avail[i, dev_m]):
            return False
        gi = (groups[i] - {dev_n}) | {dev_m}
        gj = (groups[j] - {dev_m}) | {dev_n}
        old = [self.group_cost(i, groups[i]), self.group_cost(j, groups[j])]
        new = [self.group_cost(i, gi), self.group_cost(j, gj)]
        if self._permitted(old, new):
            groups[i], groups[j] = gi, gj
            assignment[dev_n], assignment[dev_m] = j, i
            return True
        return False

    # -- beyond-paper: batched steepest-descent rounds ------------------------

    def run_batched(self, init: str = "nearest", *, max_moves: int = 10_000,
                    exchange_samples: int = 64,
                    assignment: np.ndarray | None = None) -> AssociationResult:
        """Evaluate ALL candidate transfers per round in one vmapped solve and
        apply the single best permitted move (steepest descent). Convergence
        follows from the same finite-strategy/monotone argument as Thm. 3."""
        assignment = (self.initial_assignment(init) if assignment is None
                      else np.asarray(assignment).copy())
        groups = self._groups_of(assignment)
        self._check_caps(groups)
        n, k = self.sc.n_devices, self.sc.n_servers
        n_adj = 0
        trace = [self._total(groups)]
        moves = 0

        while moves < max_moves:
            # candidate transfers: (dev, src, dst)
            cands = []
            pairs = []
            for dev in range(n):
                src = int(assignment[dev])
                if len(groups[src]) <= self.min_residual:
                    continue
                for dst in range(k):
                    if dst == src or not self.avail[dst, dev]:
                        continue
                    if (self.cap is not None
                            and len(groups[dst]) >= self.cap[dst]):
                        continue
                    cands.append((dev, src, dst))
                    pairs += [(src, groups[src]), (src, groups[src] - {dev}),
                              (dst, groups[dst]), (dst, groups[dst] | {dev})]
            best = None
            if cands:
                costs = self.group_costs_batch(pairs).reshape(-1, 4)
                for (dev, src, dst), row in zip(cands, costs):
                    old = [row[0], row[2]]
                    new = [row[1], row[3]]
                    if self._permitted(old, new):
                        delta = sum(new) - sum(old)
                        if best is None or delta < best[0]:
                            best = (delta, dev, src, dst)
            if best is not None:
                _, dev, src, dst = best
                groups[src] = groups[src] - {dev}
                groups[dst] = groups[dst] | {dev}
                assignment[dev] = dst
                n_adj += 1
                moves += 1
                trace.append(self._total(groups))
                continue
            # no transfer: try a batch of sampled exchanges, apply best
            if not self._batched_exchange(assignment, groups, exchange_samples):
                break
            n_adj += 1
            moves += 1
            trace.append(self._total(groups))
        return self._finalize(assignment, groups, n_adj, moves, trace)

    def _batched_exchange(self, assignment, groups, samples: int) -> bool:
        n, k = self.sc.n_devices, self.sc.n_servers
        cands = []
        pairs = []
        for _ in range(samples):
            dev_n, dev_m = self.rng.choice(n, size=2, replace=False)
            i, j = int(assignment[dev_n]), int(assignment[dev_m])
            if i == j or not (self.avail[j, dev_n] and self.avail[i, dev_m]):
                continue
            gi = (groups[i] - {dev_n}) | {dev_m}
            gj = (groups[j] - {dev_m}) | {dev_n}
            cands.append((dev_n, dev_m, i, j, gi, gj))
            pairs += [(i, groups[i]), (i, gi), (j, groups[j]), (j, gj)]
        if not cands:
            return False
        costs = self.group_costs_batch(pairs).reshape(-1, 4)
        best = None
        for (dev_n, dev_m, i, j, gi, gj), row in zip(cands, costs):
            if self._permitted([row[0], row[2]], [row[1], row[3]]):
                delta = (row[1] + row[3]) - (row[0] + row[2])
                if best is None or delta < best[0]:
                    best = (delta, dev_n, dev_m, i, j, gi, gj)
        if best is None:
            return False
        _, dev_n, dev_m, i, j, gi, gj = best
        groups[i], groups[j] = gi, gj
        assignment[dev_n], assignment[dev_m] = j, i
        return True

    # -- bookkeeping -----------------------------------------------------------

    def _groups_of(self, assignment) -> list[frozenset]:
        # inactive devices hold only a parked bookkeeping slot in
        # ``assignment``; they belong to no group and cost nothing
        return [frozenset(np.flatnonzero((assignment == i) & self._active))
                for i in range(self.sc.n_servers)]

    def _total(self, groups) -> float:
        return float(sum(self.group_cost(i, g) for i, g in enumerate(groups)))

    def _finalize(self, assignment, groups, n_adj, n_rounds, trace) -> AssociationResult:
        k = self.sc.n_servers
        servers = np.arange(k)
        masks = np.zeros((k, self.sc.n_devices), bool)
        for i, g in enumerate(groups):
            masks[i, list(g)] = True
        sols = self.solver.solve_batch(servers, masks)
        f = np.asarray(jnp.sum(jnp.where(masks, sols.f, 0.0), axis=0))
        beta = np.asarray(jnp.sum(jnp.where(masks, sols.beta, 0.0), axis=0))
        server_cost = np.asarray(sols.cost)
        # true (15)-(17) costs span the active population only (inactive
        # devices are in no group above, so their f/beta are zero)
        act = np.flatnonzero(self._active)
        dev = self.sc.dev
        if act.size < self.sc.n_devices:
            dev = jax.tree.map(lambda x: x[act], dev)
        e, t, c = global_cost(dev, self.sc.srv,
                              jnp.asarray(np.asarray(assignment)[act]),
                              jnp.asarray(f[act]),
                              jnp.asarray(np.maximum(beta[act], 1e-9)),
                              self.sc.lp)
        return AssociationResult(
            assignment=assignment.copy(), f=f, beta=beta,
            server_cost=server_cost,
            total_cost=self._total(groups),
            true_energy=float(e), true_delay=float(t), true_cost=float(c),
            n_adjustments=n_adj, n_rounds=n_rounds, cost_trace=trace)


# ---------------------------------------------------------------------------
# §V.A benchmark schemes
# ---------------------------------------------------------------------------

def evaluate_scheme(sc: Scenario, scheme: str, *, seed: int = 0,
                    batched: bool = True, engine: str = "fast",
                    profile: str = "default", tiers=None,
                    compact: bool | str = "auto") -> AssociationResult:
    """Run one of the paper's §V.A comparison schemes end-to-end.

      hfel           — edge association + full joint RA (the paper's algorithm)
      random         — random association, full RA, no association iterations
      greedy         — nearest-server association, full RA, no iterations
      comp_opt       — association + optimal-f / uniform-beta RA
      comm_opt       — association + optimal-beta / random-f RA
      uniform        — association + uniform-beta / random-f (no RA opt.)
      proportional   — association + inverse-distance beta / random-f

    ``engine`` selects the association iterator for the iterative schemes:
      fast     — device-resident fused-sweep engine (repro.core.assoc_fast)
      batched  — host-loop steepest descent (AssociationEngine.run_batched)
      loop     — faithful Algorithm 3 (AssociationEngine.run)
    ``batched=False`` is a legacy alias for ``engine="loop"``.

    Fast-engine options: ``compact`` picks the sweep space — all run the one
    unified move-selection kernel with different slot-index maps: ``False``
    dense (K, N), ``True`` flat compacted reachable-slot (K, R),
    ``"bucketed"`` adaptive per-bucket (K_b, R_b) widths, ``"auto"`` compacts
    when availability is sparse — and ``tiers`` — a ``ra.TIER_PLANS`` plan
    name or profile tuple —
    switches to the multi-tier warm-started descent driver
    (:meth:`~repro.core.assoc_fast.FastAssociationEngine.run_tiered`), in
    which case ``profile`` only sets the engine default and the tier plan
    governs the sweeps.
    """
    kind = {"hfel": "fast", "random": "fast", "greedy": "fast",
            "comp_opt": "comp_only", "comm_opt": "comm_only",
            "uniform": "uniform", "proportional": "proportional"}[scheme]
    if scheme in ("random", "greedy"):
        eng = AssociationEngine(sc, kind=kind, seed=seed)
        init = "random" if scheme == "random" else "nearest"
        assignment = eng.initial_assignment(init)
        groups = eng._groups_of(assignment)
        return eng._finalize(assignment, groups, 0, 0,
                             [eng._total(groups)])
    init = "random"
    if not batched:
        engine = "loop"
    if engine == "fast":
        from repro.core.assoc_fast import FastAssociationEngine
        eng = FastAssociationEngine(sc, kind=kind, seed=seed,
                                    profile=profile, compact=compact)
        if tiers is not None:
            return eng.run_tiered(init, tiers=tiers)
        return eng.run(init)
    if tiers is not None:
        raise ValueError("tiered descent requires engine='fast'")
    eng = AssociationEngine(sc, kind=kind, seed=seed)
    if engine == "batched":
        return eng.run_batched(init)
    if engine == "loop":
        return eng.run(init)
    raise ValueError(engine)
