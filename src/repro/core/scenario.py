"""Random HFEL scenario generation following the paper's Table II.

Devices and edge servers are dropped uniformly in a 500m x 500m area; the
channel gain follows the standard cellular path-loss model
``PL(dB) = 128.1 + 37.6 log10(d_km)`` (the paper cites [17] for the channel
set-up). Table II values:

  Edge bandwidth             10 MHz
  Device transmit power      200 mW
  Device CPU frequency       [1, 10] GHz
  Processing density         [30, 100] cycle/bit
  Background noise           1e-8 W
  Device training size       [5, 10] MB
  Updated model size         25000 nats
  Capacitance coefficient    2e-28
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field

import numpy as np

from repro.core.cost_model import DeviceParams, LearningParams, ServerParams


@dataclass(frozen=True)
class ReachIndex:
    """Static per-server compaction maps of a (K, N) availability matrix.

    ``idx[k, r]`` is the device index occupying reachable slot ``r`` of
    server ``k`` (devices in ascending order, padded with 0 past the server's
    reach count); ``valid[k, r]`` marks real slots; ``slot[k, n]`` inverts the
    map (slot of device ``n`` at server ``k``, or ``r_max`` when ``n`` is out
    of reach — a deliberate out-of-range sentinel so one-hot encodings of an
    invalid slot are all-zero). ``r_max`` is the compacted buffer width
    shared by all servers: the widest reach count plus headroom
    (:func:`flat_width`), so that reach counts can grow under churn without
    a new width.
    """

    idx: np.ndarray        # (K, R) int32
    valid: np.ndarray      # (K, R) bool
    slot: np.ndarray       # (K, N) int32, r_max == "unreachable"
    r_max: int

    @property
    def widest(self) -> int:
        """The widest reach count (``r_max`` less the headroom)."""
        return int(self.valid.sum(axis=1).max()) if self.valid.size else 0

    @property
    def density(self) -> float:
        return float(self.valid.mean())

    @property
    def padded_fraction(self) -> float:
        """Fraction of slots up to the widest reach count that are padding:
        the waste that skewed reach counts cause (the headroom past the
        widest count is not counted)."""
        widest = self.widest
        if not widest:
            return 0.0
        return 1.0 - float(self.valid.sum()) / (self.valid.shape[0] * widest)


@dataclass(frozen=True)
class ReachBucket:
    """One width-bucket of :class:`ReachBuckets`: the servers whose reach
    count shares a binary magnitude, compacted at that bucket's own width."""

    servers: np.ndarray    # (K_b,) int32 global server ids
    idx: np.ndarray        # (K_b, R_b) int32 device per slot (0-padded)
    valid: np.ndarray      # (K_b, R_b) bool — real slots
    width: int             # R_b = widest reach count in this bucket
    key: int = -1          # binary magnitude ceil(log2(count)) of its servers


@dataclass(frozen=True)
class ReachBuckets:
    """Adaptive-width compaction maps: servers grouped into binary buckets by
    reach count (same power-of-two scheme as ``GroupSolver.solve_batch``'s
    chunking), each bucket compacted to its own slot width R_b instead of
    every server padding to the global max R. ``slot``/``bucket_of``/
    ``row_of`` locate any (server, device) pair: device ``n`` lives at slot
    ``slot[k, n]`` of row ``row_of[k]`` in bucket ``bucket_of[k]`` (slot
    ``r_max`` is the shared out-of-reach sentinel — it is >= every bucket
    width, so per-bucket ``slot < R_b`` tests reject it)."""

    buckets: tuple[ReachBucket, ...]
    bucket_of: np.ndarray  # (K,) int32
    row_of: np.ndarray     # (K,) int32 — row within the owning bucket
    slot: np.ndarray       # (K, N) int32, r_max == "unreachable"
    r_max: int

    @property
    def padded_fraction(self) -> float:
        total = sum(b.idx.size for b in self.buckets)
        real = sum(int(b.valid.sum()) for b in self.buckets)
        return 1.0 - real / max(total, 1)


def _fill_reach_row(reach: np.ndarray, idx_row: np.ndarray,
                    valid_row: np.ndarray, slot_row: np.ndarray,
                    sentinel: int) -> None:
    """Write ONE server's compacted row in place — the ONE place slot
    numbering / padding semantics live, shared by the from-scratch builder
    and both incremental patchers: ascending device ids in the leading
    slots (0-padded past the reach count), matching validity flags, and the
    inverse slot map with ``sentinel`` marking out-of-reach devices."""
    idx_row[:] = 0
    valid_row[:] = False
    idx_row[:reach.size] = reach
    valid_row[:reach.size] = True
    slot_row[:] = sentinel
    slot_row[reach] = np.arange(reach.size, dtype=np.int32)


#: Lane width of the chip's vector registers: flat reach maps are allocated
#: in whole multiples of it (:func:`flat_width`).
LANES = 128


def flat_width(count: int, n_devices: int) -> int:
    """Allocated width of a flat reach map whose widest reach count is
    ``count``: at least an eighth more than ``count`` and one slot, rounded
    up to whole :data:`LANES` (a narrower row fills the same vector tiles
    on the chip), and never more than ``n_devices``. A churn tick then keeps
    the width, and every compiled shape, until some server's reach count
    passes it."""
    want = count + count // 8 + 1
    return min(-(-want // LANES) * LANES, n_devices)


def reach_index_map(avail: np.ndarray, *, bucketed: bool = False,
                    active: np.ndarray | None = None):
    """Compute the compacted reachable-set index maps of ``avail`` (K, N).

    The fused candidate sweeps in :mod:`repro.core.assoc_fast` run in this
    compacted (K, R) slot space: with sparse availability R << N, so both the
    number of candidate groups per refresh and the vector width of every
    group solve shrink by the reach density. Every server must reach at least
    one device only if it is ever used; zero-reach *devices* are rejected
    because they cannot be associated anywhere (constraint 17e).

    The flat map is allocated at :func:`flat_width` of the widest count.
    ``bucketed=True`` returns :class:`ReachBuckets` instead: servers are
    grouped by ``ceil(log2(reach_count))`` and each bucket is compacted at
    its own width, so one dense-reach server no longer pads every other
    server's row to the global max (see ``padded_fraction``).

    ``active`` (N,) bool restricts the maps to the active device population
    of a churn scenario: inactive devices occupy no slot anywhere (they can
    never be candidates) and are exempt from the must-reach-one check.
    """
    avail = np.asarray(avail, dtype=bool)
    if active is not None:
        avail = avail & np.asarray(active, dtype=bool)[None, :]
    need_reach = (np.ones(avail.shape[1], bool) if active is None
                  else np.asarray(active, dtype=bool))
    if not avail.any(axis=0)[need_reach].all():
        raise ValueError("every device must reach at least one server")
    k, n = avail.shape
    counts = avail.sum(axis=1)
    r_max = int(counts.max()) if k else 0

    def fill(servers, width, slot):
        """Fill one group's (idx, valid) rows and its servers' slot-map
        rows via :func:`_fill_reach_row`."""
        idx = np.zeros((len(servers), width), dtype=np.int32)
        valid = np.zeros((len(servers), width), dtype=bool)
        for row, srv in enumerate(servers):
            _fill_reach_row(np.flatnonzero(avail[srv]), idx[row],
                            valid[row], slot[srv], r_max)
        return idx, valid

    if not bucketed:
        r_max = flat_width(r_max, n)
        slot = np.full((k, n), r_max, dtype=np.int32)
        idx, valid = fill(range(k), r_max, slot)
        return ReachIndex(idx=idx, valid=valid, slot=slot, r_max=r_max)
    slot = np.full((k, n), r_max, dtype=np.int32)

    # binary bucketing: key = ceil(log2(count)); a zero-reach server (legal
    # when it is simply never used) joins the narrowest bucket
    keys = np.array([max(int(c) - 1, 0).bit_length() for c in counts])
    buckets = []
    bucket_of = np.zeros(k, dtype=np.int32)
    row_of = np.zeros(k, dtype=np.int32)
    for b, key in enumerate(sorted(set(keys.tolist()))):
        servers = np.flatnonzero(keys == key).astype(np.int32)
        width = max(int(counts[servers].max()), 1)
        idx, valid = fill(servers, width, slot)
        bucket_of[servers] = b
        row_of[servers] = np.arange(servers.size, dtype=np.int32)
        buckets.append(ReachBucket(servers=servers, idx=idx, valid=valid,
                                   width=width, key=int(key)))
    return ReachBuckets(buckets=tuple(buckets), bucket_of=bucket_of,
                        row_of=row_of, slot=slot, r_max=r_max)


@dataclass
class Scenario:
    dev: DeviceParams
    srv: ServerParams
    avail: np.ndarray            # (K, N) bool — device n can reach server i
    dist: np.ndarray             # (K, N) meters
    lp: LearningParams = field(default_factory=LearningParams)
    # Dynamic-scenario state (device churn / mobility). ``active`` marks the
    # devices currently present; ``None`` means everyone (the static case).
    # Positions and the reach radius are kept so perturb_scenario can drift
    # devices and recompute exactly the touched dist/avail columns.
    active: np.ndarray | None = None     # (N,) bool, None == all active
    dev_xy: np.ndarray | None = None     # (N, 2) meters
    srv_xy: np.ndarray | None = None     # (K, 2) meters
    reach_m: float | None = None
    # Per-edge admission capacity: server i can hold at most ``max_devices[i]``
    # active members (production edges have hard compute/memory/uplink caps;
    # the paper's eq. 17 model lets any reachable edge absorb everyone).
    # ``None`` = unlimited, the paper-faithful default. Capacities are
    # churn-invariant: perturb_scenario carries them unchanged and
    # diff_scenarios rejects scenarios whose caps differ.
    max_devices: np.ndarray | None = None  # (K,) int, None == no caps

    @property
    def n_devices(self) -> int:
        return self.dev.n_devices

    @property
    def n_servers(self) -> int:
        return self.srv.n_servers

    @property
    def active_mask(self) -> np.ndarray:
        """(N,) bool — always materialized, all-True when ``active`` unset."""
        if self.active is None:
            return np.ones(self.n_devices, dtype=bool)
        return np.asarray(self.active, dtype=bool)

    @property
    def eff_avail(self) -> np.ndarray:
        """Effective availability: reachability restricted to active devices
        (an inactive device can associate with no one)."""
        if self.active is None:
            return np.asarray(self.avail, dtype=bool)
        return np.asarray(self.avail, dtype=bool) & self.active_mask[None, :]

    @property
    def capacity(self) -> np.ndarray | None:
        """Validated (K,) int64 per-edge capacity, or ``None`` when the
        scenario is uncapacitated. The single normalization point every
        capacity consumer (engines, repair, admission) reads."""
        if self.max_devices is None:
            return None
        cap = np.asarray(self.max_devices, dtype=np.int64)
        if cap.shape != (self.n_servers,):
            raise ValueError(
                f"max_devices must have shape ({self.n_servers},), "
                f"got {cap.shape}")
        if (cap < 1).any():
            raise ValueError("max_devices entries must be >= 1")
        return cap


# ---------------------------------------------------------------------------
# Dynamic scenarios: seeded perturbations + incremental reach maintenance
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ScenarioDelta:
    """Record of one :func:`perturb_scenario` step — everything an
    incremental consumer needs to patch static state instead of rebuilding.

    ``stale_servers`` is the conservative invalidation set for per-server
    caches keyed on the *scenario* (slot-index maps, gathered per-slot
    constants, toggle-cost rows): every server whose effective reachable set
    changed, plus every server reaching a moved device in the old or new
    scenario (distance-derived quantities may differ even when reach did
    not). Association-state invalidation (groups whose membership the warm
    start repairs) is the consumer's to add on top.
    """

    seed: int
    moved: np.ndarray          # (N,) bool — position (dist column) changed
    arrived: np.ndarray        # (N,) bool — inactive -> active
    departed: np.ndarray       # (N,) bool — active -> inactive
    avail_flips: np.ndarray    # (K, N) bool — raw reachability bits flipped
    eff_flips: np.ndarray      # (K, N) bool — effective (active-masked) flips
    stale_servers: np.ndarray  # (K,) bool — see docstring

    @property
    def touched_devices(self) -> np.ndarray:
        return (self.moved | self.arrived | self.departed
                | self.avail_flips.any(axis=0))


def perturb_scenario(sc: Scenario, *, seed: int, drift_m: float = 50.0,
                     move_frac: float = 0.1, flip_frac: float = 0.0,
                     depart_frac: float = 0.0, arrive_frac: float = 0.0
                     ) -> tuple[Scenario, ScenarioDelta]:
    """One seeded, deterministic churn step: device mobility (Gaussian
    position drift re-deriving the touched dist/avail columns), per-device
    reach flips (blockage: one random server bit per picked device), and
    arrivals/departures via the ``active`` mask.

    Device/server physical parameters (and hence every RA constant) are held
    fixed — in particular the per-device channel gain, whose shadowing draw
    dominates its within-area distance spread — so group costs change ONLY
    through membership and reachability. That is the invariant incremental
    consumers rely on: an unchanged group's cached cost stays valid across
    the delta.

    Fractions are of the eligible population (active for departures/moves/
    flips, inactive for arrivals). EVERY device — active or parked — is
    guaranteed at least its nearest server after the step (constraint 17e
    repair), so ``reach_index_map(new.avail, active=new.active)`` always
    succeeds AND the parked-slot rules (``nearest raw-reachable server``)
    stay well defined for inactive devices. This is the reach invariant the
    generators promise and the property tests pin: drift and reach flips can
    empty a device's row mid-step, but never in the returned scenario.
    Returns ``(new_scenario, delta)``; ``sc`` itself is not mutated.
    """
    if sc.dev_xy is None or sc.srv_xy is None or sc.reach_m is None:
        raise ValueError(
            "perturb_scenario needs positions and reach_m on the Scenario "
            "(rebuild it with make_scenario/make_large_scenario)")
    rng = np.random.default_rng(seed)
    n, k = sc.n_devices, sc.n_servers
    active_old = sc.active_mask
    avail_old = np.asarray(sc.avail, dtype=bool)

    def pick(mask: np.ndarray, frac: float) -> np.ndarray:
        cand = np.flatnonzero(mask)
        m = min(int(round(frac * cand.size)), cand.size)
        out = np.zeros(n, dtype=bool)
        if m:
            out[rng.choice(cand, size=m, replace=False)] = True
        return out

    departed = pick(active_old, depart_frac)
    arrived = pick(~active_old, arrive_frac)
    active_new = (active_old & ~departed) | arrived

    moved = pick(active_new, move_frac)
    dev_xy = np.asarray(sc.dev_xy, dtype=float).copy()
    dist = np.asarray(sc.dist, dtype=float).copy()
    avail = avail_old.copy()
    if moved.any():
        dev_xy[moved] += rng.normal(0.0, drift_m,
                                    size=(int(moved.sum()), 2))
        dist[:, moved] = np.linalg.norm(
            np.asarray(sc.srv_xy)[:, None, :] - dev_xy[None, moved, :],
            axis=-1)
        avail[:, moved] = dist[:, moved] <= sc.reach_m

    flipped = pick(active_new, flip_frac)
    if flipped.any():
        cols = np.flatnonzero(flipped)
        rows = rng.integers(0, k, cols.size)
        avail[rows, cols] = ~avail[rows, cols]

    # 17e repair over ALL devices: flips/moves only ever touch active
    # columns, but repairing inactive columns too keeps the all-device
    # reach invariant robust on hand-built scenarios (parked slots read
    # raw reach, so a zero row there would poison the repair paths)
    nearest = np.argmin(dist, axis=0)
    bad = ~avail.any(axis=0)
    avail[nearest[bad], bad] = True

    avail_flips, eff_flips, stale = _delta_flips(
        avail_old, active_old, avail, active_new, moved)

    sc_new = dataclasses.replace(sc, avail=avail, dist=dist,
                                 active=active_new, dev_xy=dev_xy)
    delta = ScenarioDelta(seed=seed, moved=moved, arrived=arrived,
                          departed=departed, avail_flips=avail_flips,
                          eff_flips=eff_flips, stale_servers=stale)
    return sc_new, delta


def _same_params(a, b) -> bool:
    """True when two parameter dataclasses hold equal arrays (identity
    short-circuits the common case: ``perturb_scenario`` carries the very
    same dev/srv objects across ticks)."""
    if a is b:
        return True
    return all(np.array_equal(np.asarray(getattr(a, f.name)),
                              np.asarray(getattr(b, f.name)))
               for f in dataclasses.fields(a))


def _delta_flips(avail_old: np.ndarray, active_old: np.ndarray,
                 avail_new: np.ndarray, active_new: np.ndarray,
                 moved: np.ndarray
                 ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The ONE derivation of a delta's ``(avail_flips, eff_flips,
    stale_servers)`` — shared by :func:`perturb_scenario` (single tick) and
    :func:`diff_scenarios` (multi-tick diff), so the conservative staleness
    rule incremental consumers rely on cannot diverge between the two:
    every server whose effective reachable set changed, plus every server
    reaching a moved device in the old or new scenario (distance-derived
    quantities may differ even when reach did not)."""
    avail_flips = avail_new != avail_old
    eff_flips = ((avail_new & active_new[None, :])
                 != (avail_old & active_old[None, :]))
    stale = eff_flips.any(axis=1)
    if moved.any():
        stale |= avail_old[:, moved].any(axis=1)
        stale |= avail_new[:, moved].any(axis=1)
    return avail_flips, eff_flips, stale


def diff_scenarios(sc_old: Scenario, sc_new: Scenario) -> ScenarioDelta:
    """Recover a :class:`ScenarioDelta` by diffing two same-shaped scenarios.

    This is the multi-tick composition the live training loop needs when the
    association engine re-solves less often than the scenario churns:
    ``perturb_scenario`` deltas describe single ticks, and replaying them one
    at a time would force one incremental re-solve per tick. Diffing the
    scenario at the last re-solve against the current one yields the single
    combined delta ``FastAssociationEngine.rerun_incremental`` expects —
    with the same conservative ``stale_servers`` semantics (servers whose
    effective reach changed, plus servers reaching a moved device in either
    scenario). A device that departed and returned between the endpoints
    cancels out, exactly as it should for cache invalidation purposes
    (``seed`` is -1: a diff has no generating seed).
    """
    if (sc_old.n_devices != sc_new.n_devices
            or sc_old.n_servers != sc_new.n_servers):
        raise ValueError("diff_scenarios requires same-shaped scenarios")
    caps_match = ((sc_old.max_devices is None) == (sc_new.max_devices is None)
                  and (sc_old.max_devices is None
                       or np.array_equal(np.asarray(sc_old.max_devices),
                                         np.asarray(sc_new.max_devices))))
    if not (_same_params(sc_old.dev, sc_new.dev)
            and _same_params(sc_old.srv, sc_new.srv)
            and sc_old.lp == sc_new.lp and caps_match):
        # caches keyed on RA constants survive a delta ONLY because device/
        # server/learning params (and per-edge caps) are churn-invariant;
        # diffing two unrelated scenarios would silently poison every
        # incremental consumer
        raise ValueError(
            "diff_scenarios requires churn-invariant device/server/learning "
            "parameters and capacities (only avail/dist/active/dev_xy may "
            "differ)")
    active_old = sc_old.active_mask
    active_new = sc_new.active_mask
    avail_old = np.asarray(sc_old.avail, dtype=bool)
    avail_new = np.asarray(sc_new.avail, dtype=bool)
    moved = (np.asarray(sc_old.dist) != np.asarray(sc_new.dist)).any(axis=0)
    arrived = active_new & ~active_old
    departed = active_old & ~active_new
    avail_flips, eff_flips, stale = _delta_flips(
        avail_old, active_old, avail_new, active_new, moved)
    return ScenarioDelta(seed=-1, moved=moved, arrived=arrived,
                         departed=departed, avail_flips=avail_flips,
                         eff_flips=eff_flips, stale_servers=stale)


@dataclass(frozen=True)
class DeviceClientBridge:
    """Index bridge between a Scenario's device axis and a federated
    dataset's client axis — the seam the live co-simulation crosses every
    round (``Scenario.active`` -> trainer ``client_mask``, device->server
    assignment -> per-client assignment).

    ``device_of[c]`` is the device backing client ``c``; ``client_of[n]`` is
    the client backed by device ``n`` (or -1 for a device with no client —
    legal when the scenario models more devices than the dataset has
    clients). The default bridge is the identity prefix."""

    device_of: np.ndarray   # (n_clients,) int32
    client_of: np.ndarray   # (n_devices,) int32, -1 = no client

    @property
    def n_clients(self) -> int:
        return int(self.device_of.shape[0])

    @property
    def n_devices(self) -> int:
        return int(self.client_of.shape[0])

    def client_mask(self, devices: np.ndarray) -> np.ndarray:
        """Map any device-axis boolean mask (``Scenario.active``, an arrival
        set, ...) onto the client axis; devices backing no client drop out."""
        return np.asarray(devices, dtype=bool)[self.device_of]

    def client_assignment(self, assignment: np.ndarray) -> np.ndarray:
        """Map a device->server assignment onto the client axis."""
        return np.asarray(assignment)[self.device_of]


def device_client_bridge(sc: Scenario, n_clients: int,
                         device_of: np.ndarray | None = None
                         ) -> DeviceClientBridge:
    """Build (and validate) the device<->client bridge for ``sc``.

    ``device_of`` defaults to the identity prefix ``arange(n_clients)`` —
    client ``c`` is device ``c`` — which requires ``n_clients <= N``. An
    explicit ``device_of`` may map clients to any distinct devices.
    """
    n = sc.n_devices
    if device_of is None:
        if n_clients > n:
            raise ValueError(
                f"dataset has {n_clients} clients but the scenario only "
                f"{n} devices; pass an explicit device_of mapping")
        device_of = np.arange(n_clients, dtype=np.int32)
    device_of = np.asarray(device_of, dtype=np.int32)
    if device_of.shape != (n_clients,):
        raise ValueError(f"device_of must have shape ({n_clients},)")
    if device_of.size and (device_of.min() < 0 or device_of.max() >= n):
        raise ValueError("device_of entries must be valid device indices")
    if np.unique(device_of).size != device_of.size:
        raise ValueError("device_of must map clients to distinct devices")
    client_of = np.full(n, -1, dtype=np.int32)
    client_of[device_of] = np.arange(n_clients, dtype=np.int32)
    return DeviceClientBridge(device_of=device_of, client_of=client_of)


def _changed_rows(eff: np.ndarray, row_sets: list[np.ndarray]) -> np.ndarray:
    """Servers whose stored reachable set (``row_sets[s]`` = ascending device
    ids) no longer matches ``eff[s]`` — the default delta detector when the
    caller has no :class:`ScenarioDelta` at hand."""
    out = np.zeros(eff.shape[0], dtype=bool)
    for s in range(eff.shape[0]):
        reach = np.flatnonzero(eff[s])
        out[s] = (reach.size != row_sets[s].size
                  or not np.array_equal(reach, row_sets[s]))
    return out


def update_reach_index(ri: ReachIndex, avail: np.ndarray, *,
                       active: np.ndarray | None = None,
                       changed_servers: np.ndarray | None = None
                       ) -> tuple[ReachIndex, bool]:
    """Incrementally patch a flat :class:`ReachIndex` across an availability
    delta: changed servers' idx/valid/slot rows are rewritten at the map's
    existing allocated width (kept even when the new max reach count is
    smaller, so compiled shapes downstream survive); if any server's reach
    count overflows the allocated width the map is rebuilt from scratch, at
    a new width with headroom (:func:`flat_width`).

    Returns ``(new_map, rebuilt)``. ``ri`` is not mutated.
    """
    eff = np.asarray(avail, dtype=bool)
    if active is not None:
        eff = eff & np.asarray(active, dtype=bool)[None, :]
    k, n = eff.shape
    counts = eff.sum(axis=1)
    if k and int(counts.max()) > ri.r_max:
        return reach_index_map(avail, active=active), True
    if changed_servers is None:
        changed_servers = _changed_rows(
            eff, [ri.idx[s, ri.valid[s]] for s in range(k)])
    idx, valid, slot = ri.idx.copy(), ri.valid.copy(), ri.slot.copy()
    for s in np.flatnonzero(np.asarray(changed_servers, dtype=bool)):
        _fill_reach_row(np.flatnonzero(eff[s]), idx[s], valid[s], slot[s],
                        ri.r_max)
    return ReachIndex(idx=idx, valid=valid, slot=slot, r_max=ri.r_max), False


def update_reach_buckets(rbk: ReachBuckets, avail: np.ndarray, *,
                         active: np.ndarray | None = None,
                         changed_servers: np.ndarray | None = None
                         ) -> tuple[ReachBuckets, list]:
    """Incrementally maintain :class:`ReachBuckets` across an availability
    delta.

    A changed server whose reach count stays inside its bucket's binary
    magnitude (same ``ceil(log2(count))`` key) and allocated width R_b gets
    its idx/valid/slot rows patched; a server that overflows (key change, or
    count beyond R_b) forces a rebuild of every bucket it leaves or joins —
    and ONLY those. Untouched buckets keep their arrays, so per-bucket
    compiled shapes and cached per-row state survive small deltas. The
    out-of-reach sentinel only ever grows (``max(old r_max, new widths)``);
    when it grows, stale sentinel entries in unchanged slot rows are
    remapped, so ``slot < R_b`` tests stay sound everywhere.

    Returns ``(new_rbk, carry)``: ``carry[b]`` is the old bucket index whose
    (servers, width) layout new bucket ``b`` preserves — per-row caches
    indexed by that layout stay aligned — or ``None`` for rebuilt buckets.
    ``rbk`` is not mutated.
    """
    eff = np.asarray(avail, dtype=bool)
    if active is not None:
        eff = eff & np.asarray(active, dtype=bool)[None, :]
    k, n = eff.shape
    counts = eff.sum(axis=1)
    keys_new = np.array([max(int(c) - 1, 0).bit_length() for c in counts])
    if changed_servers is None:
        sets = [None] * k
        for b in rbk.buckets:
            for row, srv in enumerate(b.servers):
                sets[srv] = b.idx[row, b.valid[row]]
        changed_servers = _changed_rows(eff, sets)
    changed = np.flatnonzero(np.asarray(changed_servers, dtype=bool))

    rebuild_keys: set[int] = set()
    patch: list[int] = []
    for s in changed:
        bk = rbk.buckets[rbk.bucket_of[s]]
        if int(keys_new[s]) == bk.key and int(counts[s]) <= bk.width:
            patch.append(int(s))
        else:
            rebuild_keys.add(bk.key)
            rebuild_keys.add(int(keys_new[s]))

    members = {key: np.flatnonzero(keys_new == key).astype(np.int32)
               for key in rebuild_keys}
    new_widths = [max(int(counts[m].max()), 1)
                  for m in members.values() if m.size]
    sentinel = max([rbk.r_max] + new_widths)
    slot = rbk.slot.copy()
    if sentinel > rbk.r_max:
        # valid slots are always < their bucket width <= the old sentinel,
        # so entries equal to it are exactly the out-of-reach markers
        slot[slot == rbk.r_max] = sentinel

    def fill_rows(servers, width):
        idx = np.zeros((len(servers), width), dtype=np.int32)
        valid = np.zeros((len(servers), width), dtype=bool)
        for row, srv in enumerate(servers):
            _fill_reach_row(np.flatnonzero(eff[srv]), idx[row], valid[row],
                            slot[srv], sentinel)
        return idx, valid

    new_buckets: list[ReachBucket] = []
    carry: list = []
    for ob, bk in enumerate(rbk.buckets):
        if bk.key in rebuild_keys:
            srvs = members[bk.key]
            if srvs.size:
                idx, valid = fill_rows(srvs, max(int(counts[srvs].max()), 1))
                new_buckets.append(ReachBucket(
                    servers=srvs, idx=idx, valid=valid,
                    width=idx.shape[1], key=bk.key))
                carry.append(None)
            continue
        in_bucket = [s for s in patch if rbk.bucket_of[s] == ob]
        if in_bucket:
            idx, valid = bk.idx.copy(), bk.valid.copy()
            for s in in_bucket:
                row = rbk.row_of[s]
                _fill_reach_row(np.flatnonzero(eff[s]), idx[row],
                                valid[row], slot[s], sentinel)
            bk = ReachBucket(servers=bk.servers, idx=idx, valid=valid,
                             width=bk.width, key=bk.key)
        new_buckets.append(bk)
        carry.append(ob)
    existing = {b.key for b in rbk.buckets}
    for key in sorted(rebuild_keys - existing):
        srvs = members[key]
        if srvs.size:
            idx, valid = fill_rows(srvs, max(int(counts[srvs].max()), 1))
            new_buckets.append(ReachBucket(servers=srvs, idx=idx, valid=valid,
                                           width=idx.shape[1], key=key))
            carry.append(None)

    bucket_of = np.zeros(k, dtype=np.int32)
    row_of = np.zeros(k, dtype=np.int32)
    for b, bk in enumerate(new_buckets):
        bucket_of[bk.servers] = b
        row_of[bk.servers] = np.arange(bk.servers.size, dtype=np.int32)
    return ReachBuckets(buckets=tuple(new_buckets), bucket_of=bucket_of,
                        row_of=row_of, slot=slot, r_max=sentinel), carry


def pairwise_dist(srv_xy: np.ndarray, dev_xy: np.ndarray, *,
                  chunk: int = 16_384) -> np.ndarray:
    """(K, N) server-device distances, chunked along the device axis.

    The obvious broadcast ``norm(srv_xy[:, None] - dev_xy[None], axis=-1)``
    materializes a (K, N, 2) float64 intermediate — ~800 MB at K=500 /
    N=100k — before reducing; chunking caps the intermediate at
    (K, chunk, 2) while writing into the one (K, N) output that is needed
    anyway. Chunk boundaries do not change any element's arithmetic, so the
    result is bit-identical to the dense broadcast.
    """
    srv_xy = np.asarray(srv_xy, dtype=float)
    dev_xy = np.asarray(dev_xy, dtype=float)
    k, n = srv_xy.shape[0], dev_xy.shape[0]
    out = np.empty((k, n), dtype=np.float64)
    for lo in range(0, max(n, 1), chunk):
        sl = slice(lo, min(lo + chunk, n))
        out[:, sl] = np.linalg.norm(
            srv_xy[:, None, :] - dev_xy[None, sl, :], axis=-1)
    return out


def channel_gain_from_distance(dist_m: np.ndarray) -> np.ndarray:
    """h = 10^(-PL/10), PL = 128.1 + 37.6 log10(d_km)."""
    d_km = np.maximum(dist_m, 1.0) / 1000.0
    pl_db = 128.1 + 37.6 * np.log10(d_km)
    return 10.0 ** (-pl_db / 10.0)


def make_scenario(n_devices: int, n_servers: int, *, seed: int = 0,
                  area_m: float = 500.0, reach_m: float = 10_000.0,
                  cap_slack: float | None = None,
                  lp: LearningParams | None = None) -> Scenario:
    """Sample a random scenario with Table II parameters.

    ``reach_m`` bounds which servers a device may associate with (N_i in the
    paper); the default makes every server reachable, matching the paper's
    fully-dense evaluation (availability is then only distance-ranked).
    ``cap_slack`` (optional) generates per-edge ``max_devices`` caps sized
    ``ceil(cap_slack * nearest-count)`` — see :func:`_capacities`.
    """
    rng = np.random.default_rng(seed)
    dev_xy = rng.uniform(0.0, area_m, size=(n_devices, 2))
    srv_xy = rng.uniform(0.0, area_m, size=(n_servers, 2))
    return _assemble(rng, dev_xy, srv_xy, reach_m, lp, cap_slack)


def make_large_scenario(n_devices: int, n_servers: int, *, seed: int = 0,
                        area_m: float | None = None,
                        reach_m: float | None = None,
                        spread_m: float = 120.0,
                        cap_slack: float | None = None,
                        lp: LearningParams | None = None) -> Scenario:
    """Cluster-structured scenario for the large regimes the association
    scaling benchmarks exercise — construction is memory-safe up to
    N~100k / K~500 (distances are computed in device-axis chunks, never
    materializing a (K, N, 2) intermediate).

    Unlike :func:`make_scenario`'s fixed 500m box, the area grows with the
    server count (constant server density), devices drop as Gaussian clusters
    of width ``spread_m`` around a random anchor server, and ``reach_m``
    defaults to a *restricted* radius so availability is sparse — each device
    can reach only its nearby handful of servers, the realistic multi-cell
    regime (every device is still guaranteed its nearest server). At the
    50k+ scales, tighten ``spread_m`` (e.g. 60) so per-server reach counts —
    and with them the sweep's toggle-cache width — stay bounded as N grows.
    ``cap_slack`` generates binding-by-construction per-edge caps; ``None``
    (default) keeps the paper's uncapacitated model, bit-identical to
    previous releases.
    """
    rng = np.random.default_rng(seed)
    area = area_m if area_m is not None else 500.0 * np.sqrt(n_servers / 5.0)
    reach = reach_m if reach_m is not None else 3.0 * spread_m
    srv_xy = rng.uniform(0.0, area, size=(n_servers, 2))
    anchor = rng.integers(0, n_servers, n_devices)
    dev_xy = np.clip(srv_xy[anchor]
                     + rng.normal(0.0, spread_m, size=(n_devices, 2)),
                     0.0, area)
    return _assemble(rng, dev_xy, srv_xy, reach, lp, cap_slack)


def _capacities(dist: np.ndarray, cap_slack: float) -> np.ndarray:
    """Per-edge ``max_devices`` sized from the nearest-server load profile.

    Server ``j`` gets ``max(1, ceil(cap_slack * |{i : nearest(i)=j}|))``
    slots. ``cap_slack`` slightly above 1.0 leaves headroom over the
    all-nearest assignment (caps rarely bind); below 1.0 forces spill onto
    second-choice edges (caps bind by construction). Deterministic in the
    geometry — consumes NO rng draws, so adding caps to a generator call
    never shifts the sampled device/server parameters.
    """
    if cap_slack <= 0.0:
        raise ValueError(f"cap_slack must be > 0, got {cap_slack}")
    nearest_count = np.bincount(np.argmin(dist, axis=0),
                                minlength=dist.shape[0])
    return np.maximum(1, np.ceil(cap_slack * nearest_count)).astype(np.int32)


def _assemble(rng: np.random.Generator, dev_xy: np.ndarray,
              srv_xy: np.ndarray, reach_m: float,
              lp: LearningParams | None,
              cap_slack: float | None = None) -> Scenario:
    """Draw Table II device/server parameters for given node positions."""
    f32 = np.float32
    n_devices = dev_xy.shape[0]
    n_servers = srv_xy.shape[0]
    dist = pairwise_dist(srv_xy, dev_xy)

    data_bits = rng.uniform(5e6, 10e6, n_devices) * 8.0          # 5-10 MB
    density = rng.uniform(30.0, 100.0, n_devices)                # cycle/bit
    # Power-law client sample counts (non-IID sizing per [20]); used only as
    # aggregation weights |D_n| — the physical compute load uses data_bits.
    samples = np.floor(rng.pareto(2.0, n_devices) * 200 + 50)

    # Per-device channel gain to its geometrically nearest server. The
    # within-area gain spread is modest, so a single h_n per device (as the
    # paper's Table I implies) is a faithful simplification.
    nearest = np.argmin(dist, axis=0)
    h = channel_gain_from_distance(dist[nearest, np.arange(n_devices)])
    h *= rng.lognormal(0.0, 0.5, n_devices)                      # shadowing

    dev = DeviceParams(
        cycles_per_iter=(density * data_bits).astype(f32),
        data_samples=samples.astype(f32),
        model_nats=np.full(n_devices, 25_000.0, f32),
        tx_power=np.full(n_devices, 0.2, f32),
        channel_gain=h.astype(f32),
        alpha=np.full(n_devices, 2e-28, f32),
        f_min=np.full(n_devices, 1e9, f32),
        f_max=np.full(n_devices, 10e9, f32),
    )
    srv = ServerParams(
        bandwidth=np.full(n_servers, 10e6, f32),
        noise=np.full(n_servers, 1e-8, f32),
        cloud_rate=rng.uniform(0.5e5, 1.5e5, n_servers).astype(f32),
        cloud_power=np.full(n_servers, 1.0, f32),
        cloud_nats=np.full(n_servers, 25_000.0, f32),
    )
    avail = dist <= reach_m
    # Constraint (17e) requires every device to be associable somewhere.
    unreachable = ~avail.any(axis=0)
    avail[nearest[unreachable], unreachable] = True

    return Scenario(dev=dev, srv=srv, avail=avail, dist=dist,
                    lp=lp or LearningParams(),
                    dev_xy=dev_xy.copy(), srv_xy=srv_xy.copy(),
                    reach_m=float(reach_m),
                    max_devices=(None if cap_slack is None
                                 else _capacities(dist, cap_slack)))
