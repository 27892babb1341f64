"""Device-resident edge association — ONE fused candidate-sweep kernel with
an incremental toggle-cost delta cache, parameterised by slot-index maps.

This is the performance engine behind Algorithm 3 / ``run_batched``: the whole
steepest-descent adjustment loop runs inside ONE jitted ``lax.while_loop``
with donated state buffers, enqueued right behind the program that fills
the toggle-cost cache, so a full association run costs a single host
round-trip regardless of how many adjustments it applies. The reference
:class:`~repro.core.edge_association.AssociationEngine` instead drives every
round through Python loops, frozenset-keyed memo dicts, and one
``solve_batch`` host->device sync per candidate batch.

Unified slot-space design
-------------------------
Association state is a dense ``(K, N)`` boolean membership mask plus, per
*bucket* of servers, a compacted toggle-cost cache::

    toggle_b[row, r] = group cost of  member[server] XOR {device at slot r}
    cur[server]      = group cost of  member[server]

Because XOR adds a device when it is absent and removes it when present,
``toggle`` simultaneously caches every "group gains device" candidate (for
non-members) and every "group loses device" candidate (for members) — the
two halves of any transfer. The delta of moving device ``n`` from its server
``s = assign[n]`` to server ``k`` is then pure arithmetic::

    delta = (toggle[s at n's slot] - cur[s]) + (toggle[k at n's slot] - cur[k])

so each steepest-descent round scans ALL reachable transfer candidates with
zero solver calls, picks the best permitted move via ``lax`` reductions with
an explicit device-major tie-break key, and only then refreshes the cache. A
move touches exactly two servers, so the refresh solves each touched server's
current group plus its single-slot toggles — ``R_b + 1`` groups of vector
width ``R_b``. With one bucket and no sharding (the dense and flat compact
spaces) both rows go through ONE batched solve of ``2 (R + 1)`` groups: the
solver's sequential depth does not grow with its batch, so this costs far
less than two solves in turn. Bucketed and sharded sweeps dispatch each row
to its server's bucket with its own ``lax.switch``, since the two rows may
differ in width or live on different shards.

There is exactly one move-selection loop body (:func:`_run_device`); the
historical dense / compacted engines are *configurations* of it:

* **dense** (``compact=False``): one bucket whose index maps are the
  identity (``idx[k] = arange(N)``, every slot exists, candidate slots
  gated by ``avail``). The sweep then runs in the classic (K, N) space.
* **flat compact** (``compact=True``, auto-on for sparse reach): one bucket
  built from :func:`repro.core.scenario.reach_index_map` — all servers pad
  to the global max reach count R, and the per-move refresh solves
  ``R + 1`` groups of width R, an ``(N/R)^2``-ish cut versus dense that is
  what makes full N=2000/K=50 convergence runs tractable.
* **bucketed** (``compact="bucketed"``): adaptive slot widths.
  ``reach_index_map(avail, bucketed=True)`` groups servers into binary
  buckets by reach count (the same power-of-two scheme as
  ``GroupSolver.solve_batch``), each compacted at its own width ``R_b``, so
  one dense-reach server no longer pads every other server's row. The sweep
  evaluates one fused candidate scan per bucket and merges the per-bucket
  argmins with the same global device-major tie-break key, so move selection
  is order-identical to the flat configurations.

Padded slots carry garbage toggle costs by construction and are excluded
from every candidate mask; they never influence a move. The dense ``(K, N)``
mask stays the single source of truth: compacted membership rows are
gathered from it on demand (``member[servers[row], idx[row]] & exists``), so
applying a move is two dense column writes — no per-bucket scatter state to
keep consistent.

Sampled *exchanges* (Definition 5) ride the same fused sweep: when no
transfer is permitted, a ``lax.cond`` branch draws candidate device pairs
with the on-device PRNG, evaluates both swapped groups for every pair in ONE
vmapped solve in a shared all-server slot space (``ex_bucket``, flat width;
sampled pairs hit arbitrary server pairs, so pricing them once per width
bucket would multiply the solve work), and applies the best permitted swap
followed by the same two-row cache refresh in the per-bucket caches.
Swapped masks are built by XOR-ing one-hot slot encodings — an out-of-reach
slot encodes as the all-zero row, so unavailable swaps are naturally inert
and additionally gated.

Sharded sweep (``shards=p``)
----------------------------
For the N=50k+ regimes one device cannot price a sweep fast enough, the
same move-selection impl runs under ``shard_map`` over a ``p``-device mesh:
every bucket's rows (servers) are padded to a multiple of ``p`` and
partitioned along :data:`_SHARD_AXIS`, so each shard prices only its own
servers' candidate scans and R_b+1-group refreshes. Membership, assignment
and the (K, N) slot map stay replicated; per-shard (1, K) locator slices
mark foreign servers with a sentinel bucket id that dispatches to the
existing no-op refresh branch. Cross-shard consistency costs three
collectives per concern — ``psum`` over disjoint single-owner contributions
(bitwise exact: every other shard adds 0.0) for cache init / removal-toggle
gathers / post-move ``cur`` re-replication, and one ``all_gather`` +
lexicographic (delta, device-major order) fold that reproduces the
sequential bucket fold's move selection exactly. A sharded sweep therefore
applies the identical move sequence as the single-device program, and
``shards=None`` (the default) does not even trace the collectives — the
historical bit-exact graph is untouched. Sampled exchanges distribute too:
the pair *proposal* stays replicated — every shard splits the same key and
draws the identical ``(S, 2)`` batch, preserving the ``shards=None`` RNG
stream bit-for-bit — while the 2S candidate group-cost solves (the
expensive part) are index-partitioned across shards in contiguous sample
chunks, and the winning swap is selected by the same ``all_gather`` +
lexicographic (delta, sample-index order) fold the transfer path uses
(contiguous chunks make the per-shard argmin reproduce ``argmin``'s
first-occurrence tie-break globally). The apply step and the two-row cache
refresh then run exactly like a transfer's. On CPU, multi-device meshes
come from ``XLA_FLAGS=--xla_force_host_platform_device_count=<p>``.

``ra_backend="pallas"`` additionally routes every batched group solve of
the ``fast`` kind through the fused golden-section kernel
(:mod:`repro.kernels.golden_section`) instead of the vmapped op-by-op XLA
graph — one kernel call per refresh (2 (R + 1) groups when both rows are
fused, R_b + 1 otherwise). It matches the XLA solver to float32 rounding
(not bit-exactly), so the default stays ``"xla"``.

Two-tier descent (:meth:`FastAssociationEngine.run_tiered`)
-----------------------------------------------------------
Screening profiles trade solve accuracy for sweep speed but leave a ~1% cost
gap at the stable point. The tiered driver runs the adjustment loop once per
profile of a :data:`repro.core.resource_allocation.TIER_PLANS` plan (default
``"two_tier"`` = coarse then default), warm-starting each tier from the
previous tier's stable assignment. The coarse tier applies nearly all moves
cheaply; the default-accuracy polish then needs only a handful of moves to
recover the reference-accuracy stable point, at a fraction of a default-only
sweep's wall time. The concatenated ``cost_trace`` keeps each tier's
evaluation seam (tier boundaries re-evaluate the same assignment at the new
profile's accuracy, so the trace is monotone within tiers, not across them).

The per-group solver is :func:`repro.core.edge_association.solve_group`, so
every §V.A scheme kind works here; ``profile`` selects a
:data:`repro.core.resource_allocation.SCREEN_PROFILES` iteration preset
("default" reproduces the reference engine bit-for-bit on the solve level,
"screen"/"coarse" cut sweep cost ~2-4x for large-N scenarios).

Compilation: one cache-init program (:func:`_init_cache`) per (bucket
shape tuple, ``kind``, ``profile``, cold or warm start) and one move-loop
program per (bucket shape tuple, ``max_moves``, ``exchange_samples``,
``kind``, ``profile``, ``permission``, ``min_residual``). The jit caches
are module-global, so repeated engines on same-shaped scenarios reuse the
compiled programs.

Tracing (:mod:`repro.utils.tracing`, off by default): host spans
``hfel.build.{solver,reach,space}``, ``hfel.init_assign``,
``hfel.sweep.init``, ``hfel.sweep.loop`` (each device span closes once the
device is done), ``hfel.readback``, ``hfel.finalize``,
``hfel.rerun.{patch,repair}``, one ``hfel.count`` event per sweep
carrying the :data:`COUNT_NAMES` counters, and one ``hfel.rerun.count``
event per warm re-solve carrying the :data:`RERUN_COUNT_NAMES` counts.
The device code carries the ``named_scope`` names ``hfel.init``,
``hfel.scan``, ``hfel.exchange``, ``hfel.refresh`` and
``hfel.solve.{xla,pallas}``.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import resource_allocation as ra
from repro.core.cost_model import cloud_delay, cloud_energy, global_cost
from repro.core.edge_association import (AssociationResult, GroupSolver,
                                         NoFeasibleServerError,
                                         greedy_admission, initial_assignment,
                                         nearest_feasible, parked_slots,
                                         solve_group)
from repro.core.scenario import (ReachBuckets, ReachIndex, Scenario,
                                 ScenarioDelta, reach_index_map,
                                 update_reach_buckets, update_reach_index)
from repro.utils import tracing

_INF = jnp.inf
_I32_BIG = np.iinfo(np.int32).max

# Mesh axis name of the sharded sweep (see "Sharded sweep" in the module
# docstring): server-bucket rows are partitioned along it, everything else
# is replicated.
_SHARD_AXIS = "servers"

# ``compact="auto"`` promotes flat compaction to the bucketed adaptive-width
# sweep when the flat map wastes more than this fraction of its slots on
# padding. Measured (experiments/bench_results.json, assoc_scale/compaction):
# at padded fraction 0.353 (N=1000/K=20) bucketed sweeps are 1.63x faster
# per move than flat; near zero padding the per-bucket dispatch overhead
# wins nothing, so the threshold sits between the two regimes.
BUCKETED_AUTO_THRESHOLD = 0.25

#: The engine-wide default sampled-exchange budget (Definition 5 escape
#: moves per stuck round). ONE default everywhere — ``run``, ``run_tiered``,
#: ``rerun_incremental``, ``LiveHFELRunner``/``run_live`` — so no driver
#: silently drops the stochastic-escape path; pass ``exchange_samples=0``
#: explicitly for a deterministic transfer-only sweep.
DEFAULT_EXCHANGE_SAMPLES = 64

#: Work counters of one sweep, in the order of the int32 vector that the
#: device programs carry; ``FastAssociationEngine.last_counts`` names them.
#: ``init_groups``: group solves issued at cache init (on a warm start, the
#: stale rows' only); ``stale_rows``: servers re-priced at init (K when
#: cold); ``iterations``: move-loop iterations; ``transfers``/``exchanges``:
#: moves applied of each kind; ``exchange_tries``: iterations that took the
#: exchange branch; ``loop_groups``: group solves issued by the refreshes
#: and the exchange pricing; ``fused_refreshes``: iterations whose two
#: touched rows were refreshed in one batched solve (every applied move of
#: a single-bucket unsharded sweep, 0 elsewhere). Under sharding the solve
#: counts cover the padded rows and samples every shard prices.
COUNT_NAMES = ("init_groups", "stale_rows", "iterations", "transfers",
               "exchange_tries", "exchanges", "loop_groups",
               "fused_refreshes")

#: Host counts of one warm re-solve (:meth:`FastAssociationEngine.
#: rerun_incremental`), added to ``last_counts`` after the sweep's: active
#: devices that ``departed``, parked ones that ``arrived``, active devices
#: ``displaced`` from a server they no longer reach, and ``reach_rebuilds``,
#: reach maps rebuilt at a new width (the flat map, or each rebuilt bucket;
#: a new width compiles the sweep again).
RERUN_COUNT_NAMES = ("departed", "arrived", "displaced", "reach_rebuilds")


class _Bucket(NamedTuple):
    """One slot-width bucket of the unified sweep: the per-server index maps
    plus every RA constant pre-gathered into (K_b, R_b) slot space."""

    servers: jnp.ndarray    # (K_b,) global server ids
    idx: jnp.ndarray        # (K_b, R_b) device id per slot
    exists: jnp.ndarray     # (K_b, R_b) slot holds a real device
    ok: jnp.ndarray         # (K_b, R_b) slot is a legal transfer target
    consts: object          # RAConstants, leaves gathered per bucket row
    random_f: jnp.ndarray   # (K_b, R_b)
    inv_dist: jnp.ndarray   # (K_b, R_b)


def _bucket_cost_fn(kind, profile, bucket, cloud_const):
    """(bucket_row, slot_mask) -> group cost incl. the non-empty cloud
    constant of the row's server."""

    def cost(row, mask):
        c = jax.tree.map(lambda x: x[row], bucket.consts)
        with jax.named_scope("hfel.solve.xla"):
            sol = solve_group(kind, c, mask, random_f=bucket.random_f[row],
                              inv_dist_row=bucket.inv_dist[row],
                              profile=profile)
        return sol.cost + jnp.where(jnp.any(mask),
                                    cloud_const[bucket.servers[row]], 0.0)

    return cost


def _bucket_costs_fn(kind, profile, bucket, cloud_const, ra_backend):
    """Batched ``(rows (M,), masks (M, R_b)) -> (M,) group costs`` for one
    bucket. ``ra_backend="xla"`` vmaps the scalar :func:`_bucket_cost_fn`
    (the historical, bit-exact path); ``"pallas"`` routes the ``fast`` kind
    through the fused golden-section kernel, solving the whole batch in one
    kernel call instead of a vmapped op-by-op graph."""
    if ra_backend == "pallas":
        iters = ra.SCREEN_PROFILES[profile]

        def costs(rows, masks):
            cb = jax.tree.map(lambda x: x[rows], bucket.consts)
            with jax.named_scope("hfel.solve.pallas"):
                sol = ra.solve_fixed_point_batched(cb, masks,
                                                   backend="pallas", **iters)
            return sol.cost + jnp.where(jnp.any(masks, axis=-1),
                                        cloud_const[bucket.servers[rows]],
                                        0.0)

        return costs
    return jax.vmap(_bucket_cost_fn(kind, profile, bucket, cloud_const))


def _merge_sum(x, axis):
    """Re-replicate disjoint single-owner contributions (every non-owner
    shard contributes exact 0.0, so the psum is bitwise the owner's value);
    identity on the single-device path."""
    return lax.psum(x, axis) if axis is not None else x


def _counts(**vals):
    """An int32 work-counter vector (:data:`COUNT_NAMES` order) with the
    named entries set and the others 0."""
    return jnp.stack([jnp.asarray(vals.get(name, 0), jnp.int32)
                      for name in COUNT_NAMES])


def _rows_costs_fn(buckets, cloud_const, kind, profile, ra_backend):
    """``(b, member, rows) -> (m, R_b + 1)``: each bucket-``b`` row's current
    group cost followed by its R_b single-slot toggle costs, with the
    membership gathered from the dense mask (padded slots forced False)."""
    cost_vs = [_bucket_costs_fn(kind, profile, bd, cloud_const, ra_backend)
               for bd in buckets]
    eyes = [jnp.eye(bd.idx.shape[1], dtype=bool) for bd in buckets]

    def rows_costs(b, member, rows):
        bd = buckets[b]
        rb = bd.idx.shape[1]
        base = (member[bd.servers[rows][:, None], bd.idx[rows]]
                & bd.exists[rows])                             # (m, rb)
        masks = jnp.concatenate(
            [base[:, None, :], base[:, None, :] ^ eyes[b][None]], axis=1)
        sids = jnp.repeat(rows, rb + 1)
        return cost_vs[b](sids, masks.reshape(-1, rb)).reshape(
            rows.shape[0], rb + 1)

    return rows_costs


@partial(jax.jit, donate_argnums=(3,),
         static_argnames=("kind", "profile", "ra_backend"))
def _init_cache(member, buckets, cloud_const, kept=None, stale=None, *,
                kind, profile, ra_backend="xla"):
    """Fill every bucket's toggle-cost cache: the first of the sweep's two
    device programs (the move loop, :func:`_run_device`, is the second).

    ``kept`` is ``None`` (cold start: every cache row is solved) or the
    previous run's cache ``(cur_prev (K,), toggles_prev per bucket)`` with
    ``stale`` (K,) bool — the incremental-rerun path: rows of non-stale
    servers are copied from the previous cache and only stale rows pay the
    R_b+1 group solves, which is what makes re-convergence under small
    scenario deltas cheap. ``kept`` is donated: its buffers become the new
    cache.

    Returns ``(cur (K,), toggles per bucket, counts)``; ``counts`` is the
    :data:`COUNT_NAMES` vector with ``init_groups`` and ``stale_rows`` set.
    """
    return _init_cache_impl(member, buckets, cloud_const, kept, stale,
                            axis=None, kind=kind, profile=profile,
                            ra_backend=ra_backend)


def _init_cache_impl(member, buckets, cloud_const, kept, stale, *, axis,
                     kind, profile, ra_backend):
    """Cache-init body shared by :func:`_init_cache` and its ``shard_map``
    twin (:func:`_sharded_init`), like :func:`_run_device_impl`. Each shard
    fills its own bucket rows; ``cur`` is re-replicated by ``psum``."""
    k = member.shape[0]
    rows_costs = _rows_costs_fn(buckets, cloud_const, kind, profile,
                                ra_backend)
    # one server at a time: lax.map keeps peak memory at one server's
    # (R_b+1, R_b) batch, which is what allows N=2000-scale scenarios on a
    # single host. On a warm start the per-row cond skips the solves for
    # rows the delta left valid; the row still flows through the map so
    # shapes never change.
    cur = jnp.zeros(k, jnp.float32)
    toggles = []
    groups = jnp.asarray(0, jnp.int32)
    with jax.named_scope("hfel.init"):
        for b, bd in enumerate(buckets):
            kb, rb = bd.idx.shape
            if kept is None:
                def row_fn(rw, b=b):
                    return rows_costs(b, member, rw[None])[0]
                groups = groups + kb * (rb + 1)
            else:
                cur_prev, toggles_prev = kept

                def row_fn(rw, b=b):
                    srv = buckets[b].servers[rw]
                    old_row = jnp.concatenate([cur_prev[srv][None],
                                               toggles_prev[b][rw]])
                    return lax.cond(
                        stale[srv],
                        lambda _: rows_costs(b, member, rw[None])[0],
                        lambda _: old_row, None)
                # the same (clamped) gather the cond reads: a sharded
                # padding row is priced with its clamped server's flag
                groups = groups + jnp.sum(stale[bd.servers],
                                          dtype=jnp.int32) * (rb + 1)
            costs = lax.map(row_fn, jnp.arange(kb, dtype=jnp.int32))
            cur = cur.at[bd.servers].set(costs[:, 0])
            toggles.append(costs[:, 1:])
    stale_rows = k if kept is None else jnp.sum(stale, dtype=jnp.int32)
    return (_merge_sum(cur, axis), tuple(toggles),
            _counts(init_groups=_merge_sum(groups, axis),
                    stale_rows=stale_rows))


@partial(jax.jit, donate_argnums=(0, 1, 3, 4, 5),
         static_argnames=("kind", "profile", "permission", "min_residual",
                          "max_moves", "exchange_samples", "ra_backend"))
def _run_device(member, assignment, key, cur, toggles, counts, buckets,
                ex_bucket, slot_of, bucket_of, row_of, cloud_const, cap,
                rel_tol, *, kind, profile, permission, min_residual,
                max_moves, exchange_samples, ra_backend="xla"):
    """The whole adjustment loop as one device program — the single
    move-selection kernel behind every sweep space (dense / flat compact /
    bucketed; see module docstring). It starts from the toggle-cost cache
    ``cur``/``toggles`` that :func:`_init_cache` filled, and ``counts``,
    the counter vector that program returned.

    ``buckets`` is a static-length tuple of :class:`_Bucket`; ``slot_of``
    (K, N) maps (server, device) to the device's slot in the server's bucket
    (out-of-range when unreachable), ``bucket_of``/``row_of`` (K,) locate
    each server's toggle row. ``ex_bucket`` is a single bucket covering ALL
    K servers (rows = server ids) in which exchange candidates are priced —
    sampled exchange pairs hit arbitrary server pairs, so evaluating them in
    one shared slot space avoids solving every pair once per width bucket.

    ``cap`` is the traced (K,) int32 per-edge admission capacity: a server
    at cap rejects inbound transfers (exchanges are 1-for-1, hence
    cap-neutral and never gated). The uncapacitated engine passes a cap of
    N everywhere — an inbound transfer needs a donor group elsewhere, so
    ``gsize < N`` always holds and the gate selects exactly the historical
    moves. Traced, not static: toggling caps never recompiles.

    Returns (member, assignment, cur, toggles, n_moves, trace, counts);
    ``trace[i]`` is the surrogate total after move i (trace[0] = initial
    total), padded with NaN past ``n_moves``; ``counts`` adds the loop's
    counters to the init's.
    """
    return _run_device_impl(member, assignment, key, cur, toggles, counts,
                            buckets, ex_bucket, slot_of, bucket_of, row_of,
                            cloud_const, cap, rel_tol, axis=None, kind=kind,
                            profile=profile, permission=permission,
                            min_residual=min_residual, max_moves=max_moves,
                            exchange_samples=exchange_samples,
                            ra_backend=ra_backend)


def _run_device_impl(member, assignment, key, cur0, toggles0, counts0,
                     buckets, ex_bucket, slot_of, bucket_of, row_of,
                     cloud_const, cap, rel_tol, *, axis, axis_size=1, kind,
                     profile, permission, min_residual, max_moves,
                     exchange_samples, ra_backend):
    """Adjustment-loop body shared by the single-device jit
    (:func:`_run_device`, ``axis=None`` — traced graph identical to the
    historical kernel, so single-device results stay bit-exact) and the
    ``shard_map`` wrapper (:func:`_sharded_runner`, ``axis=_SHARD_AXIS``,
    ``axis_size`` = mesh size).

    Under sharding every bucket's rows are padded to a multiple of the mesh
    size and partitioned along axis 0; padded rows carry the sentinel server
    id K (scatters drop it, gathers clamp, ``exists``/``ok`` are False so it
    never becomes a candidate). ``bucket_of``/``row_of`` arrive as this
    shard's (1, K) locator slice whose sentinel bucket id ``len(buckets)``
    means "server owned by another shard" — it dispatches to the same no-op
    ``lax.switch`` branch that an unapplied move uses. Cross-shard state
    stays consistent through three collectives per concern: ``psum`` of
    disjoint single-owner contributions (cache init, removal-toggle gather,
    post-move ``cur`` re-replication — bitwise exact, every summand but one
    is 0.0) and an ``all_gather`` + lexicographic (delta, order) fold that
    reproduces the sequential bucket fold's device-major move selection
    exactly, so a sharded sweep applies the identical move sequence.

    Sampled exchanges distribute with the same split (module docstring,
    "Sharded sweep"): replicated pair proposal, sample-chunk-partitioned
    candidate pricing, all_gather + (delta, sample index) winner fold.

    The work counters ride the loop state and never feed a value. Group
    solves are counted on the shard that issues them (padded exchange
    samples included) and summed over shards at the end; the move and
    iteration counts are replicated.
    """
    k, n = member.shape
    nb = len(buckets)
    i32 = jnp.int32
    idx_n = jnp.arange(n)
    # contiguous per-shard exchange-sample chunks: shard s prices global
    # samples [s*ex_chunk, (s+1)*ex_chunk); ceil-division padding samples
    # carry okay=False so they can never win
    ex_chunk = -(-exchange_samples // axis_size) if exchange_samples else 0
    ex_pad = ex_chunk * axis_size - exchange_samples
    if axis is not None:
        # this shard's locator slice: (1, K) -> (K,)
        bucket_of = bucket_of.reshape(-1)
        row_of = row_of.reshape(-1)

    def merge_sum(x):
        return _merge_sum(x, axis)

    rows_costs = _rows_costs_fn(buckets, cloud_const, kind, profile,
                                ra_backend)
    ex_cost_v = _bucket_costs_fn(kind, profile, ex_bucket, cloud_const,
                                 ra_backend)
    r_ex = ex_bucket.idx.shape[1]
    # group solves of one refresh per bucket, and 0 for the no-op branch
    refresh_groups = jnp.asarray([bd.idx.shape[1] + 1 for bd in buckets]
                                 + [0], i32)
    # one bucket, unsharded (the dense and flat compact spaces): both
    # touched rows are refreshed in one batched solve (refresh_pair).
    # Bucketed rows may differ in width and sharded rows in owner, so those
    # keep one lax.switch per row (refresh_server).
    fused = nb == 1 and axis is None

    trace0 = jnp.full(max_moves + 1, jnp.nan, cur0.dtype)
    trace0 = trace0.at[0].set(jnp.sum(cur0))

    def harmless(new, old):
        return new <= old + rel_tol * jnp.maximum(old, 1e-9)

    def removal_toggle(toggles, assign):
        """Per device: toggle cost of its current server losing it, gathered
        across buckets (each server's row lives in exactly one)."""
        sl = slot_of[assign, idx_n]                            # (n,)
        out = jnp.zeros(n, cur0.dtype)
        for b, bd in enumerate(buckets):
            kb, rb = bd.idx.shape
            v = toggles[b][jnp.clip(row_of[assign], 0, kb - 1),
                           jnp.clip(sl, 0, rb - 1)]
            out = jnp.where(bucket_of[assign] == b, v, out)
        return merge_sum(out)

    def can_join(srv, dev):
        """Availability gate for device(s) joining server(s), elementwise
        (ex_bucket rows are server ids, so no per-bucket dispatch needed)."""
        sl = slot_of[srv, dev]
        return (sl < r_ex) & ex_bucket.ok[srv, jnp.clip(sl, 0, r_ex - 1)]

    def refresh_server(member, server, applied, cur, toggles):
        """Refresh one touched server's cur + toggle row in its own bucket
        via lax.switch (extra branch = no-op when the move wasn't applied).
        Also returns the group solves that refresh issued on this shard."""

        def branch(b):
            def go(ops):
                cur, toggles = ops
                row = row_of[server]
                costs = rows_costs(b, member, row[None])       # (1, rb+1)
                return (cur.at[server].set(costs[0, 0]),
                        tuple(t.at[row].set(costs[0, 1:]) if i == b else t
                              for i, t in enumerate(toggles)))
            return go

        which = jnp.where(applied, bucket_of[server], nb)
        with jax.named_scope("hfel.refresh"):
            cur, toggles = lax.switch(
                which, [branch(b) for b in range(nb)] + [lambda ops: ops],
                (cur, toggles))
        return cur, toggles, refresh_groups[which]

    def refresh_pair(member, servers, applied, cur, toggles):
        """Refresh both touched servers' rows in ONE batched solve of
        2 (R + 1) groups: with one bucket and no sharding both rows share
        the width and live on this device, and the sequential depth of a
        batched solve does not grow with its batch. The two servers differ
        (a transfer has src != dst, an exchange si != sj), so the writes
        are those of two single-row refreshes."""

        def go(ops):
            cur, (tog,) = ops
            rows = row_of[servers]
            costs = rows_costs(0, member, rows)                # (2, R+1)
            return (cur.at[servers].set(costs[:, 0]),
                    (tog.at[rows].set(costs[:, 1:]),))

        with jax.named_scope("hfel.refresh"):
            cur, toggles = lax.cond(applied, go, lambda ops: ops,
                                    (cur, toggles))
        return cur, toggles, jnp.where(applied, 2 * refresh_groups[0], 0)

    def body(state):
        member, assign, cur, toggles, moves, key, trace, _, work = state
        # -- scan all reachable transfer candidates from the cache (no
        #    solves), one fused scan per bucket, argmins merged globally --
        with jax.named_scope("hfel.scan"):
            cur_src = cur[assign]                              # (n,)
            minus = removal_toggle(toggles, assign)            # (n,)
            minus_delta = minus - cur_src
            gsize = jnp.sum(member, axis=1)                    # (k,)
            if permission == "pareto":
                src_harmless = harmless(minus, cur_src)        # (n,)

            best_delta = jnp.asarray(_INF, cur0.dtype)
            best_order = jnp.asarray(_I32_BIG, i32)
            t_dev = jnp.asarray(0, i32)
            t_dst = jnp.asarray(0, i32)
            for b, bd in enumerate(buckets):
                rb = bd.idx.shape[1]
                dev = bd.idx                                   # (kb, rb)
                cur_b = cur[bd.servers][:, None]               # (kb, 1)
                src = assign[dev]                              # (kb, rb)
                delta = minus_delta[dev] + toggles[b] - cur_b
                scale = jnp.maximum(cur_b + cur_src[dev], 1e-9)
                # capacity feasibility rides the same per-row mask as the
                # residual-group rule: a destination at cap admits no
                # inbound transfer (sentinel-padded rows are already
                # ok=False, and the clamped cap gather there is harmless)
                headroom = (gsize[bd.servers] < cap[bd.servers])[:, None]
                valid = (bd.ok & (src != bd.servers[:, None])
                         & (gsize[src] > min_residual) & headroom)
                permitted = valid & (delta < -rel_tol * scale)
                if permission == "pareto":
                    permitted &= (harmless(toggles[b], cur_b)
                                  & src_harmless[dev])
                masked = jnp.where(permitted, delta, _INF)
                bucket_best = jnp.min(masked)
                # explicit device-major order key reproduces the host
                # reference engine's argmin tie-breaking (smallest n*K + k
                # among equal deltas) — globally, across buckets
                order = dev.astype(i32) * k + bd.servers[:, None].astype(i32)
                tie = jnp.where(masked == bucket_best, order, _I32_BIG)
                p = jnp.argmin(tie)
                b_order = tie.reshape(-1)[p]
                take = ((bucket_best < best_delta)
                        | ((bucket_best == best_delta)
                           & (b_order < best_order)))
                best_delta = jnp.where(take, bucket_best, best_delta)
                best_order = jnp.where(take, b_order, best_order)
                t_dev = jnp.where(take, dev.reshape(-1)[p], t_dev)
                t_dst = jnp.where(take, bd.servers[p // rb], t_dst)
            if axis is not None:
                # merge the per-shard winners with the SAME lexicographic
                # (delta, device-major order) rule the bucket fold above
                # uses, so the sharded sweep selects the identical move
                deltas = lax.all_gather(best_delta, axis)      # (p,)
                orders = lax.all_gather(best_order, axis)
                g_delta = jnp.min(deltas)
                g_tie = jnp.where(deltas == g_delta, orders, _I32_BIG)
                shard = jnp.argmin(g_tie)
                best_delta = g_delta
                best_order = g_tie[shard]
                t_dev = lax.all_gather(t_dev, axis)[shard]
                t_dst = lax.all_gather(t_dst, axis)[shard]
        has_transfer = jnp.isfinite(best_delta)
        t_src = assign[t_dev]

        def do_transfer(args):
            member, assign, key = args
            m2 = member.at[t_src, t_dev].set(False).at[t_dst, t_dev].set(True)
            a2 = assign.at[t_dev].set(t_dst)
            return (jnp.asarray(True), jnp.stack([t_src, t_dst]), m2, a2, key)

        def no_exchange(args):
            member, assign, key = args
            return (jnp.asarray(False), jnp.zeros(2, i32), member, assign,
                    key)

        def do_exchange(args):
            member, assign, key = args
            # the pair PROPOSAL is replicated under sharding: every shard
            # splits the same key and draws the identical (S, 2) batch, so
            # the shards=None RNG stream is preserved bit-for-bit
            # hfellint: disable=HFEL007 -- replicated-key by design
            key, sub = jax.random.split(key)
            pairs = jax.random.randint(sub, (exchange_samples, 2), 0, n,
                                       dtype=i32)
            dn, dm = pairs[:, 0], pairs[:, 1]
            si, sj = assign[dn], assign[dm]
            okay = ((dn != dm) & (si != sj)
                    & can_join(sj, dn) & can_join(si, dm))

            def onehot(srv, dev):
                # an out-of-reach slot encodes as the all-zero row
                return jnp.arange(r_ex)[None, :] == slot_of[srv, dev][:, None]

            def ex_base(rows):
                return (member[ex_bucket.servers[rows][:, None],
                               ex_bucket.idx[rows]]
                        & ex_bucket.exists[rows])

            def price(dn_, dm_, si_, sj_, okay_):
                """Masked exchange deltas of a (sub)batch of sampled pairs —
                per-sample arithmetic identical on both paths, so chunked
                sharded pricing is bitwise the single-device pricing."""
                m = dn_.shape[0]
                gi = ex_base(si_) ^ onehot(si_, dn_) ^ onehot(si_, dm_)
                gj = ex_base(sj_) ^ onehot(sj_, dm_) ^ onehot(sj_, dn_)
                costs = ex_cost_v(jnp.concatenate([si_, sj_]),
                                  jnp.concatenate([gi, gj]))
                ci, cj = costs[:m], costs[m:]
                old = cur[si_] + cur[sj_]
                delta = ci + cj - old
                perm = okay_ & (delta < -rel_tol * jnp.maximum(old, 1e-9))
                if permission == "pareto":
                    perm &= harmless(ci, cur[si_]) & harmless(cj, cur[sj_])
                return jnp.where(perm, delta, _INF)

            if axis is None:
                masked = price(dn, dm, si, sj, okay)
                e = jnp.argmin(masked)
                best = masked[e]
            else:
                # this shard prices only its contiguous sample chunk; the
                # winner merge below is the transfer path's all_gather +
                # lexicographic (delta, order) fold with order = global
                # sample index, which reproduces the replicated argmin's
                # first-occurrence tie-break exactly
                start = lax.axis_index(axis) * ex_chunk

                def cut(x):
                    if ex_pad:
                        pad = jnp.zeros((ex_pad,) + x.shape[1:], x.dtype)
                        x = jnp.concatenate([x, pad])
                    return lax.dynamic_slice_in_dim(x, start, ex_chunk)

                masked = price(cut(dn), cut(dm), cut(si), cut(sj), cut(okay))
                el = jnp.argmin(masked)
                deltas = lax.all_gather(masked[el], axis)      # (p,)
                orders = lax.all_gather((start + el).astype(i32), axis)
                best = jnp.min(deltas)
                g_tie = jnp.where(deltas == best, orders, _I32_BIG)
                e = jnp.clip(g_tie[jnp.argmin(g_tie)], 0,
                             exchange_samples - 1)
            applied = jnp.isfinite(best)
            ri, rj = si[e], sj[e]
            dnb, dmb = dn[e], dm[e]
            m2 = member.at[ri, dnb].set(
                jnp.where(applied, False, member[ri, dnb]))
            m2 = m2.at[rj, dnb].set(jnp.where(applied, True, m2[rj, dnb]))
            m2 = m2.at[rj, dmb].set(jnp.where(applied, False, m2[rj, dmb]))
            m2 = m2.at[ri, dmb].set(jnp.where(applied, True, m2[ri, dmb]))
            a2 = assign.at[dnb].set(jnp.where(applied, rj, assign[dnb]))
            a2 = a2.at[dmb].set(jnp.where(applied, ri, a2[dmb]))
            return (applied, jnp.stack([ri, rj]), m2, a2, key)

        args = (member, assign, key)
        if exchange_samples:
            def exchange(args):
                with jax.named_scope("hfel.exchange"):
                    return do_exchange(args)

            applied, rows, member, assign, key = lax.cond(
                has_transfer, do_transfer, exchange, args)
            tried = ~has_transfer
        else:
            applied, rows, member, assign, key = lax.cond(
                has_transfer, do_transfer, no_exchange, args)
            tried = jnp.asarray(False)
        if fused:
            cur, toggles, groups = refresh_pair(member, rows, applied, cur,
                                                toggles)
        else:
            cur, toggles, g0 = refresh_server(member, rows[0], applied, cur,
                                              toggles)
            cur, toggles, g1 = refresh_server(member, rows[1], applied, cur,
                                              toggles)
            groups = g0 + g1
        if axis is not None:
            # only the touched servers' owners re-solved their cur entries;
            # re-replicate exactly those two (psum of owner-only values)
            owned = bucket_of != nb
            touched = jnp.zeros(k, bool).at[rows].set(applied)
            fresh = merge_sum(jnp.where(touched & owned, cur, 0.0))
            cur = jnp.where(touched, fresh, cur)
        moves = moves + applied.astype(i32)
        trace = trace.at[moves].set(
            jnp.where(applied, jnp.sum(cur), trace[moves]))
        work = work + _counts(
            iterations=1, transfers=applied & has_transfer,
            exchange_tries=tried, exchanges=applied & ~has_transfer,
            loop_groups=groups + tried.astype(i32) * (2 * ex_chunk),
            fused_refreshes=applied & fused)
        return (member, assign, cur, toggles, moves, key, trace, ~applied,
                work)

    def cond(state):
        return (~state[7]) & (state[4] < max_moves)

    state = (member, assignment, cur0, toggles0, jnp.asarray(0, i32), key,
             trace0, jnp.asarray(False), _counts())
    member, assignment, cur, toggles, moves, _, trace, _, work = \
        lax.while_loop(cond, body, state)
    loop_groups = COUNT_NAMES.index("loop_groups")
    work = work.at[loop_groups].set(merge_sum(work[loop_groups]))
    return member, assignment, cur, toggles, moves, trace, counts0 + work


# jitted shard_map programs of the cache init and the move loop, keyed on
# (program, mesh devices, bucket count, statics) — module-global like the
# single-device jit caches, so repeated engines on same-shaped scenarios
# reuse the compiled programs
_SHARDED_CACHE: dict = {}


def _shard_mapped(key, body, mesh, in_specs, out_specs):
    fn = _SHARDED_CACHE.get(key)
    if fn is None:
        # check_vma=False: jax has no replication rule for lax.while_loop
        # bodies, and the impls' explicit psum/all_gather merges are what
        # keep the replicated outputs consistent
        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                   out_specs=out_specs, check_vma=False))
        _SHARDED_CACHE[key] = fn
    return fn


def _sharded_init(mesh, n_buckets: int, has_warm: bool, *, kind, profile,
                  ra_backend):
    """The sharded counterpart of :func:`_init_cache`: each shard fills its
    own bucket rows; ``cur`` and the counters come back replicated and the
    toggle caches in the global padded layout (so ``rerun_incremental``
    warm-starts work unchanged across device counts)."""
    shd, rep = P(_SHARD_AXIS), P()
    body = partial(_init_cache_impl, axis=_SHARD_AXIS, kind=kind,
                   profile=profile, ra_backend=ra_backend)
    # (member, buckets, cloud_const, kept, stale)
    in_specs = (rep, shd, rep, (rep, shd) if has_warm else rep, rep)
    return _shard_mapped(
        ("init", tuple(mesh.devices.flat), n_buckets, has_warm, kind,
         profile, ra_backend),
        body, mesh, in_specs, (rep, shd, rep))


def _sharded_runner(mesh, n_buckets: int, *, kind, profile, permission,
                    min_residual, max_moves, exchange_samples, ra_backend):
    """The sharded counterpart of :func:`_run_device`: the same impl wrapped
    in ``shard_map`` over ``mesh``. Bucket rows, their toggle caches and the
    per-shard locator slices are partitioned along :data:`_SHARD_AXIS`;
    membership, assignment and all scalars are replicated."""
    shd, rep = P(_SHARD_AXIS), P()
    body = partial(_run_device_impl, axis=_SHARD_AXIS,
                   axis_size=int(mesh.devices.size), kind=kind,
                   profile=profile, permission=permission,
                   min_residual=min_residual, max_moves=max_moves,
                   exchange_samples=exchange_samples, ra_backend=ra_backend)
    # (member, assignment, key, cur, toggles, counts, buckets, ex_bucket,
    #  slot_of, bucket_of, row_of, cloud_const, cap, rel_tol)
    in_specs = (rep, rep, rep, rep, shd, rep, shd, rep, rep, shd, shd, rep,
                rep, rep)
    out_specs = (rep, rep, rep, shd, rep, rep, rep)
    return _shard_mapped(
        (tuple(mesh.devices.flat), n_buckets, kind, profile, permission,
         min_residual, max_moves, exchange_samples, ra_backend),
        body, mesh, in_specs, out_specs)


def _dense_member(assignment: np.ndarray, active: np.ndarray,
                  n_servers: int) -> np.ndarray:
    """Dense (K, N) membership of an assignment, gated by the active mask:
    inactive devices keep a parked bookkeeping slot in ``assignment`` but
    belong to no group (and cost nothing)."""
    member = np.zeros((n_servers, assignment.shape[0]), dtype=bool)
    act = np.asarray(active, dtype=bool)
    member[np.asarray(assignment)[act], np.flatnonzero(act)] = True
    return member


def _true_cost_terms(sc: Scenario, active: np.ndarray, assignment: np.ndarray,
                     f: np.ndarray, beta: np.ndarray
                     ) -> tuple[float, float, float]:
    """Eqs. (15)-(17) over the ACTIVE population only: inactive devices hold
    no resources and must not enter the per-device energy/delay terms. A
    fully-departed population has nothing training or transmitting, so its
    round costs (0, 0, 0) — a degenerate value, not an error, because churn
    can legitimately empty a small scenario mid-simulation and the live loop
    must record the round and keep going."""
    act = np.flatnonzero(np.asarray(active, dtype=bool))
    dev = sc.dev
    if act.size == 0:
        return 0.0, 0.0, 0.0
    if act.size < sc.n_devices:
        dev = jax.tree.map(lambda x: x[act], dev)
    e, t, c = global_cost(dev, sc.srv, jnp.asarray(np.asarray(assignment)[act]),
                          jnp.asarray(np.asarray(f)[act]),
                          jnp.asarray(np.maximum(np.asarray(beta)[act],
                                                 1e-9)), sc.lp)
    return float(e), float(t), float(c)


def assignment_true_cost(sc: Scenario, assignment: np.ndarray, *,
                         solver: GroupSolver | None = None,
                         kind: str = "fast", seed: int = 0
                         ) -> tuple[float, float, float]:
    """Paper eqs. (15)-(17) ``(energy, delay, cost)`` of an explicit
    assignment on ``sc`` at reference RA accuracy, gated by the scenario's
    active mask — the per-round system-cost accounting of the live
    co-simulation (:mod:`repro.fl.live`), usable without building a full
    association engine (the ``static`` policy never sweeps).

    ``solver`` may be a prebuilt default-profile :class:`GroupSolver` to
    amortize the RA-constants build across rounds: device/server physical
    parameters are churn-invariant (the :func:`perturb_scenario` contract),
    so one solver stays valid across mobility ticks for every scheme except
    ``proportional`` (whose inverse-distance draws follow ``sc.dist``; pass
    a fresh solver per tick for that kind).
    """
    if solver is None:
        solver = GroupSolver(sc, kind, seed=seed, profile="default")
    elif solver.kind != kind:
        raise ValueError(
            f"prebuilt solver was built for kind={solver.kind!r}, "
            f"not {kind!r}")
    else:
        # the documented contract is reference accuracy: a screening-profile
        # solver (e.g. an engine's own coarse sweep solver) is viewed at the
        # default profile — with_profile shares constants, so this is free.
        # (``seed`` only matters when building; a prebuilt solver keeps its
        # own random_f draws for the fixed-f scheme kinds.)
        solver = solver.with_profile("default")
    assignment = np.asarray(assignment)
    active = sc.active_mask
    member = _dense_member(assignment, active, sc.n_servers)
    sols = solver.solve_batch(np.arange(sc.n_servers), member)
    jm = jnp.asarray(member)
    f = np.asarray(jnp.sum(jnp.where(jm, sols.f, 0.0), axis=0))
    beta = np.asarray(jnp.sum(jnp.where(jm, sols.beta, 0.0), axis=0))
    return _true_cost_terms(sc, active, assignment, f, beta)


def repair_assignment(sc_new: Scenario, prev_assign: np.ndarray,
                      old_active: np.ndarray
                      ) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                 np.ndarray]:
    """Repair a previous stable assignment onto a churned scenario — the ONE
    place the repair rules live, shared by ``rerun_incremental`` (warm path)
    and any cold re-solve that must be bit-comparable with it (the live
    loop's ``periodic-cold`` policy descends a fresh engine from exactly
    this repaired start, which is what makes the PR-4 warm/cold parity gate
    apply at every swap point).

    Rules: departures (active -> inactive) park at their nearest raw-reachable
    server (:func:`~repro.core.edge_association.parked_slots`); active
    devices whose previous server is no longer effectively reachable
    (arrivals holding a parked slot included, when that slot went out of
    reach) move to their nearest effectively-reachable server; everyone else
    keeps their slot. A displaced device with ZERO effectively-reachable
    servers raises :class:`~repro.core.edge_association.NoFeasibleServerError`
    — the old masked ``argmin`` silently parked it on server 0, poisoning
    server 0's group (and the warm/cold parity that hangs off it).

    Under ``sc_new.capacity``, keepers keep their slots (cap-feasible by
    induction: the previous stable point respected caps and the churn left
    them reachable) while displaced devices AND all arrivals are re-admitted
    greedily in device order via
    :func:`~repro.core.edge_association.greedy_admission` — an arrival's
    parked slot was never counted against a cap, so keeping it blindly
    could overflow the server. Admission failure raises the same error.

    Returns ``(assignment, departed, arrived, displaced)`` — the masks the
    caller needs for cache invalidation and trainer-state repair.
    """
    prev_assign = np.asarray(prev_assign)
    n = sc_new.n_devices
    dist = np.asarray(sc_new.dist)
    eff = np.asarray(sc_new.eff_avail)
    active = sc_new.active_mask
    old_active = np.asarray(old_active, dtype=bool)
    cap = sc_new.capacity
    departed = old_active & ~active
    arrived = active & ~old_active
    ok_now = eff[prev_assign, np.arange(n)]
    displaced = active & ~ok_now
    assign = prev_assign.copy()
    assign[departed] = parked_slots(sc_new)[departed]
    if cap is None:
        assign[displaced] = nearest_feasible(dist, eff,
                                             need=displaced)[displaced]
        return assign, departed, arrived, displaced
    readmit = displaced | arrived
    keep = active & ~readmit
    load = np.bincount(assign[keep], minlength=sc_new.n_servers)
    todo = np.flatnonzero(readmit)
    placed = greedy_admission(dist, eff, load, cap, todo)
    if (placed < 0).any():
        raise NoFeasibleServerError(todo[placed < 0], "no admitting server")
    assign[todo] = placed
    return assign, departed, arrived, displaced


class FastAssociationEngine:
    """Drop-in fast engine: same semantics as ``AssociationEngine.run_batched``
    (steepest permitted transfer per round, best sampled exchange when no
    transfer is permitted, identical permission rules and tolerances), with
    the whole loop resident on device.

    ``compact`` selects the sweep space — all of them run the SAME
    move-selection kernel, configured with different slot-index maps:
    ``False`` = dense (K, N) identity maps, ``True`` = flat compacted
    (K, R) reachable-slot space, ``"bucketed"`` = per-bucket (K_b, R_b)
    adaptive widths, and ``"auto"`` (default) picks flat compaction whenever
    availability is actually sparse (R < N). All spaces share move selection
    order, so they land on the same stable point.

    Differences from the reference: exchange candidates are drawn with the
    JAX PRNG instead of NumPy's (so exchange *sequences* differ run-to-run
    between engines), and all cost arithmetic is float32 on device rather
    than float64 on host. With ``exchange_samples=0`` the two engines are
    move-for-move identical on non-degenerate scenarios.

    ``shards=p`` runs the sweep shard_mapped over the first ``p`` jax
    devices (see "Sharded sweep" in the module docstring) — same move
    sequence, server-partitioned pricing; ``ra_backend="pallas"`` prices
    candidate groups through the fused golden-section kernel (``fast`` kind
    only). Both default off, leaving the classic bit-exact program.
    """

    def __init__(self, sc: Scenario, *, kind: str = "fast",
                 permission: str = "utilitarian", min_residual_group: int = 2,
                 seed: int = 0, rel_tol: float = 1e-5,
                 profile: str = "default", compact: bool | str = "auto",
                 shards: int | None = None, ra_backend: str = "xla"):
        assert permission in ("utilitarian", "pareto"), permission
        assert compact in (True, False, "auto", "bucketed"), compact
        if ra_backend not in ("xla", "pallas"):
            raise ValueError(f"ra_backend must be 'xla' or 'pallas', "
                             f"got {ra_backend!r}")
        if ra_backend == "pallas" and kind != "fast":
            raise ValueError(
                "ra_backend='pallas' fuses the fast kind's group solve "
                "and therefore requires kind='fast'")
        self.ra_backend = ra_backend
        # ``shards=None`` is the classic single-device program (bit-exact
        # contract); ``shards=p`` runs the SAME impl shard_mapped over the
        # first p devices — p=1 exercises the sharded program on one device
        self.shards = None if shards is None else int(shards)
        if self.shards is None:
            self._mesh = None
        else:
            devs = jax.devices()
            if not 1 <= self.shards <= len(devs):
                raise ValueError(
                    f"shards={self.shards} but only {len(devs)} device(s) "
                    "visible (force more with XLA_FLAGS="
                    "--xla_force_host_platform_device_count=<p> on CPU)")
            self._mesh = Mesh(np.array(devs[:self.shards]), (_SHARD_AXIS,))
        self.sc = sc
        self.kind = kind
        self.profile = profile
        self.permission = permission
        self.min_residual = min_residual_group
        self.rel_tol = rel_tol
        self.seed = seed
        with tracing.span("hfel.build.solver"):
            self.solver = GroupSolver(sc, kind, seed=seed, profile=profile)
            # final reporting always happens at reference accuracy so costs
            # are comparable across screening profiles (the sweep may run
            # coarser)
            self._eval_solver = self.solver.with_profile("default")
            self.rng = np.random.default_rng(seed)
            self._active = sc.active_mask
            self.avail = np.asarray(sc.eff_avail)
            # per-edge admission caps (None = the paper's uncapacitated
            # model). The kernel always takes a traced (K,) cap array;
            # uncapped engines pass N — never binding, since an inbound
            # transfer needs a donor group elsewhere — so toggling caps
            # changes no jit signature and the uncapped graph stays
            # bit-identical to the historical one.
            self.cap = sc.capacity
            self._cap = jnp.asarray(
                np.full(sc.n_servers, sc.n_devices, np.int64)
                if self.cap is None else self.cap, jnp.int32)
            self.cloud_const = jnp.asarray(
                np.asarray(sc.lp.lambda_e * cloud_energy(sc.srv)
                           + sc.lp.lambda_t * cloud_delay(sc.srv),
                           dtype=np.float32))
        self.reach: ReachIndex | None = None
        self.reach_buckets: ReachBuckets | None = None
        with tracing.span("hfel.build.reach"):
            try:
                self.reach = reach_index_map(np.asarray(sc.avail),
                                             active=self._active)
            except ValueError:
                if compact in (True, "bucketed"):
                    raise
            if compact == "auto":
                if self.reach is None or self.reach.widest >= sc.n_devices:
                    compact = False
                else:
                    # sparse reach -> compact; heavily padded flat maps
                    # (skewed reach counts) -> the bucketed adaptive-width
                    # sweep
                    compact = ("bucketed"
                               if (self.reach.padded_fraction
                                   > BUCKETED_AUTO_THRESHOLD)
                               else True)
            self.compact = ("bucketed" if compact == "bucketed"
                            else bool(compact))
            if self.compact == "bucketed":
                self.reach_buckets = reach_index_map(
                    np.asarray(sc.avail), bucketed=True, active=self._active)
        with tracing.span("hfel.build.space"):
            self._rebuild_space()
        self.last_state: dict | None = None   # debug: cur/toggle cache dump
        self.last_tier_moves: list[int] | None = None
        self.last_moves: int | None = None    # applied moves of the last sweep
        # the last sweep's work counters, by COUNT_NAMES (and, after a warm
        # re-solve, its host counts, by RERUN_COUNT_NAMES)
        self.last_counts: dict[str, int] | None = None
        self._warm_cache: dict | None = None  # rerun_incremental state
        self.last_repaired_assignment: np.ndarray | None = None

    def _rebuild_space(self) -> None:
        """(Re)derive the sweep-space buffers — per-bucket index maps with
        pre-gathered constants plus the slot/bucket/row locators — from the
        current ``self.reach``/``self.reach_buckets``/``self.avail``. Cheap
        (pure gathers); the expensive state is the toggle cache, which
        :meth:`rerun_incremental` preserves across calls to this."""
        k, n = self.sc.n_servers, self.sc.n_devices
        servers = np.arange(k, dtype=np.int32)
        if self.compact == "bucketed":
            rbk = self.reach_buckets
            raw = [(b.servers, b.idx, b.valid, b.valid) for b in rbk.buckets]
            self._slot_of = jnp.asarray(rbk.slot)
            bucket_of, row_of = rbk.bucket_of, rbk.row_of
            # exchanges hit arbitrary server pairs, so they are priced in
            # one shared flat (K, R_max) space (same slot numbering as the
            # per-bucket maps) instead of once per width bucket
            self._ex_bucket = self._gather_bucket(
                servers, self.reach.idx, self.reach.valid, self.reach.valid)
        elif self.compact:
            r = self.reach
            raw = [(servers, r.idx, r.valid, r.valid)]
            self._slot_of = jnp.asarray(r.slot)
            bucket_of = np.zeros(k, np.int32)
            row_of = servers
            self._ex_bucket = None
        else:
            # dense sweep = identity index maps: every slot exists (so an
            # out-of-reach *current* member is still priced, like the host
            # reference engine), and availability only gates candidacy
            ident = np.broadcast_to(np.arange(n, dtype=np.int32), (k, n))
            raw = [(servers, ident, np.ones((k, n), bool), self.avail)]
            self._slot_of = jnp.asarray(np.ascontiguousarray(ident))
            bucket_of = np.zeros(k, np.int32)
            row_of = servers
            self._ex_bucket = None
        if self._mesh is None:
            self._buckets = tuple(self._gather_bucket(*r) for r in raw)
            self._bucket_of = jnp.asarray(bucket_of)
            self._row_of = jnp.asarray(row_of)
        else:
            self._buckets, self._bucket_of, self._row_of = \
                self._shard_space(raw, k)
        if self._ex_bucket is None:
            self._ex_bucket = (self._buckets[0] if self._mesh is None
                               else self._gather_bucket(*raw[0]))

    def _shard_space(self, raw: list, k: int):
        """Pad every bucket's row maps to a multiple of the mesh size for
        even partitioning along :data:`_SHARD_AXIS`, and build the per-shard
        (p, K) locator slices. Padded rows carry the sentinel server id K
        (their scatters drop, their gathers clamp, exists/ok stay False);
        a locator entry of ``len(raw)`` marks a server owned by another
        shard — the sweep's no-op switch branch."""
        p = self.shards
        nb = len(raw)
        bucket_of = np.full((p, k), nb, np.int32)
        row_of = np.zeros((p, k), np.int32)
        padded = []
        for b, (srvs, idx, exists, ok) in enumerate(raw):
            srvs = np.asarray(srvs, np.int32)
            kb = srvs.shape[0]
            rows_tot = -(-kb // p) * p
            extra = rows_tot - kb
            width = idx.shape[1]
            srvs_p = np.concatenate([srvs, np.full(extra, k, np.int32)])
            idx_p = np.concatenate(
                [idx, np.zeros((extra, width), idx.dtype)])
            exists_p = np.concatenate([exists, np.zeros((extra, width), bool)])
            ok_p = np.concatenate([ok, np.zeros((extra, width), bool)])
            padded.append(self._gather_bucket(srvs_p, idx_p, exists_p, ok_p))
            rows_per = rows_tot // p
            grow = np.arange(kb)
            bucket_of[grow // rows_per, srvs] = b
            row_of[grow // rows_per, srvs] = grow % rows_per
        # place each shard's rows on its own device once, here, rather than
        # keeping the whole padded layout on the first device and scattering
        # it again on every sweep call
        rows = NamedSharding(self._mesh, P(_SHARD_AXIS))
        return jax.device_put((tuple(padded), bucket_of, row_of), rows)

    def _gather_bucket(self, servers, idx, exists, ok) -> _Bucket:
        """Pre-gather every per-device RA quantity into this bucket's
        (K_b, R_b) slot space; per-server (1-D) leaves gather by server id."""
        srv = jnp.asarray(servers, jnp.int32)
        ridx = jnp.asarray(idx)
        rows = srv[:, None]
        consts = jax.tree.map(
            lambda x: x[rows, ridx] if x.ndim == 2 else x[srv],
            self.solver.consts)
        return _Bucket(servers=srv, idx=ridx,
                       exists=jnp.asarray(exists), ok=jnp.asarray(ok),
                       consts=consts,
                       random_f=self.solver.random_f[ridx],
                       inv_dist=self.solver.inv_dist[rows, ridx])

    def initial_assignment(self, init: str = "nearest") -> np.ndarray:
        return initial_assignment(self.sc, self.avail, self.rng, init)

    def evaluate_assignment(self, assignment: np.ndarray) -> float:
        """Reference-accuracy total system cost of an explicit assignment —
        the same evaluation ``_finalize`` applies to a run's stable point, so
        costs from different screening profiles (or no run at all) compare on
        one scale."""
        assignment = np.asarray(assignment)
        n, k = self.sc.n_devices, self.sc.n_servers
        member = self._member_of(assignment)
        sols = self._eval_solver.solve_batch(np.arange(k), member)
        return float(np.sum(np.asarray(sols.cost)
                            + np.where(member.any(axis=1),
                                       np.asarray(self.cloud_const), 0.0)))

    def run(self, init: str = "nearest", *, max_moves: int = 10_000,
            exchange_samples: int = DEFAULT_EXCHANGE_SAMPLES,
            assignment: np.ndarray | None = None, finalize: bool = True):
        """One adjustment-loop descent to the stable point.

        ``exchange_samples`` defaults to :data:`DEFAULT_EXCHANGE_SAMPLES`
        (= 64) — the one engine-wide default, shared with ``run_tiered``,
        ``rerun_incremental`` and the live loop — and works under
        ``shards=p`` too (the sampled-exchange pass is distributed with a
        bit-identical winner merge; see "Sharded sweep" in the module
        docstring). Pass 0 for a deterministic transfer-only sweep.

        ``finalize=False`` mirrors :meth:`rerun_incremental`'s fast path: it
        skips the reference-accuracy ``_finalize`` evaluation and returns
        just the (N,) stable assignment (read ``last_moves`` /
        ``stable_assignment`` for the rest) — so cold and warm re-solves can
        be timed symmetrically, with cost accounting on the caller's
        schedule.
        """
        with tracing.span("hfel.init_assign"):
            assignment = (self.initial_assignment(init) if assignment is None
                          else np.asarray(assignment))
        assignment, member, moves, trace = self._sweep(
            assignment, self.profile, max_moves, exchange_samples,
            jax.random.PRNGKey(self.seed))
        if not finalize:
            return assignment.copy()
        return self._finalize(assignment, member, moves, trace)

    def run_tiered(self, init: str = "nearest", *,
                   tiers: str | tuple[str, ...] = "two_tier",
                   max_moves: int = 10_000,
                   exchange_samples: int = DEFAULT_EXCHANGE_SAMPLES,
                   tier_rel_tols: tuple[float, ...] | None = None,
                   assignment: np.ndarray | None = None) -> AssociationResult:
        """Two-tier (or n-tier) descent: drive each profile of ``tiers`` to
        its stable point, warm-starting from the previous tier's assignment.

        ``tiers`` is a :data:`repro.core.resource_allocation.TIER_PLANS` plan
        name or an explicit profile tuple; the engine's own ``profile`` is
        ignored by this driver. Coarse tiers apply the bulk of the moves at a
        fraction of default-accuracy sweep cost, and the final tier's polish
        recovers the reference-accuracy stable point. ``tier_rel_tols``
        optionally sets a per-tier stop tolerance (same length as the
        resolved plan): a looser leading tolerance stops the cheap tier at
        *near*-stability and leaves the long tail of sub-threshold moves to
        the tolerance the final tier declares stability at. The stop
        tolerance is a traced argument, so varying it never recompiles. The
        returned trace concatenates all tiers (each tier re-evaluates its
        warm start at its own accuracy, so seams may step, but every tier is
        monotone).
        """
        profiles = ra.resolve_tiers(tiers)
        rel_tols = (tuple(tier_rel_tols) if tier_rel_tols is not None
                    else (self.rel_tol,) * len(profiles))
        if len(rel_tols) != len(profiles):
            raise ValueError(
                f"tier_rel_tols has {len(rel_tols)} entries for "
                f"{len(profiles)} tiers")
        with tracing.span("hfel.init_assign"):
            assignment = (self.initial_assignment(init) if assignment is None
                          else np.asarray(assignment))
        base_key = jax.random.PRNGKey(self.seed)
        total_moves = 0
        trace: list[float] = []
        tier_moves: list[int] = []
        member = None
        for i, (prof, tol) in enumerate(zip(profiles, rel_tols)):
            assignment, member, moves, tr = self._sweep(
                assignment, prof, max_moves, exchange_samples,
                jax.random.fold_in(base_key, i), rel_tol=tol)
            total_moves += moves
            tier_moves.append(moves)
            trace.extend(tr)
        self.last_tier_moves = tier_moves
        return self._finalize(assignment, member, total_moves, trace)

    def rerun_incremental(self, sc_new: Scenario, delta: ScenarioDelta, *,
                          max_moves: int = 10_000,
                          exchange_samples: int = DEFAULT_EXCHANGE_SAMPLES,
                          verify: bool = False, finalize: bool = True):
        """Re-converge after a :func:`repro.core.scenario.perturb_scenario`
        step WITHOUT rebuilding the expensive static state.

        The engine mutates itself onto ``sc_new``: the reach slot-index maps
        are patched in place (only overflowing buckets rebuild), the
        previous stable assignment is repaired on the host (departures
        leave their groups, arrivals and out-of-reach devices go to their
        nearest effectively-reachable server), and the adjustment loop
        restarts with the previous toggle-cost cache — only the rows of
        servers the delta or the repair touched are re-solved at init. From
        a near-stable warm start the descent needs a handful of moves where
        a cold start needs hundreds.

        The sweep runs at the profile that produced the cached rows (the
        last ``run``/``run_tiered`` tier), since cache entries from another
        profile would poison move selection. Chained deltas are supported:
        each call refreshes the cache for the next.

        ``verify=True`` is the hard parity gate: a cold engine is built on
        ``sc_new`` and descended from the same repaired assignment, and the
        two stable points must match bit-identically (raises otherwise).
        It re-pays the full rebuild, so it is for tests/benchmarks, not for
        the hot path. The parity holds with ``exchange_samples > 0`` (the
        :data:`DEFAULT_EXCHANGE_SAMPLES` default): both sides descend from
        the same repaired assignment, bitwise-equal caches and the same
        ``PRNGKey(seed)`` stream, so they draw and apply the same escape
        moves.

        ``finalize=False`` is the non-verifying fast path for per-round use
        (the live co-simulation's hot loop): it skips the reference-accuracy
        ``_finalize`` evaluation — which costs a full default-profile
        ``solve_batch`` — and returns just the (N,) stable assignment.
        The stable-point cache is refreshed either way, so the next
        ``rerun_incremental`` warm-starts identically, and the assignment
        stays readable afterwards via :attr:`stable_assignment`. System-cost
        accounting then happens separately (e.g. via
        :func:`assignment_true_cost`), on the caller's schedule rather than
        once per re-solve.
        """
        if self._warm_cache is None:
            raise RuntimeError(
                "rerun_incremental needs a prior run()/run_tiered() on this "
                "engine to warm-start from")
        cache = self._warm_cache
        profile = cache["profile"]
        prev_assign = np.asarray(cache["assignment"])
        old_active = self._active
        n, k = self.sc.n_devices, self.sc.n_servers
        if sc_new.n_devices != n or sc_new.n_servers != k:
            raise ValueError("rerun_incremental requires fixed (N, K); "
                             "churn uses the active mask, not resizing")
        new_cap = sc_new.capacity
        if ((self.cap is None) != (new_cap is None)
                or (self.cap is not None
                    and not np.array_equal(self.cap, new_cap))):
            # the traced cap array is engine state built at __init__; the
            # churn contract (diff_scenarios) keeps caps invariant anyway
            raise ValueError(
                "rerun_incremental requires churn-invariant max_devices; "
                "rebuild the engine to change capacities")

        with tracing.span("hfel.rerun.patch"):
            # ---- swap the scenario and patch the static index maps ----
            self.sc = sc_new
            self._active = sc_new.active_mask.copy()
            self.avail = np.asarray(sc_new.eff_avail)
            if delta.moved.any():
                # distance-derived solver buffers (only the "proportional"
                # scheme reads them; RA constants are delta-invariant)
                inv = 1.0 / np.maximum(np.asarray(sc_new.dist), 1.0)
                self.solver.inv_dist = jnp.asarray(inv.astype(np.float32))
                self._eval_solver = self.solver.with_profile("default")
            raw = np.asarray(sc_new.avail)
            stale = np.asarray(delta.stale_servers, dtype=bool).copy()
            carry: list = [0] * len(self._buckets)
            if self.compact:
                # the flat map backs the flat sweep AND the bucketed mode's
                # shared exchange slot space; dense engines never read it after
                # __init__'s auto decision, so it is dropped rather than left
                # silently stale
                self.reach, flat_rebuilt = update_reach_index(
                    self.reach, raw, active=self._active,
                    changed_servers=delta.stale_servers)
            else:
                self.reach = None
            rebuilds = 0
            if self.compact == "bucketed":
                self.reach_buckets, carry = update_reach_buckets(
                    self.reach_buckets, raw, active=self._active,
                    changed_servers=delta.stale_servers)
                rebuilds = sum(c is None for c in carry)
            elif self.compact:
                carry = [None] if flat_rebuilt else [0]
                rebuilds = int(flat_rebuilt)
            elif self.kind == "proportional" and delta.moved.any():
                # dense toggle rows span every device, so a moved device's
                # inv_dist change can touch any row's cached cost
                stale[:] = True
            self._rebuild_space()

        with tracing.span("hfel.rerun.repair"):
            # ---- repair the previous stable assignment on the host ----
            assign, departed, arrived, displaced = repair_assignment(
                sc_new, prev_assign, old_active)
            # groups losing a member (departures + displaced previous members)
            stale[prev_assign[departed]] = True
            stale[prev_assign[displaced & old_active]] = True
            # groups gaining a member (every arrival joins *some* group)
            stale[assign[displaced]] = True
            stale[assign[arrived]] = True

            # ---- align cached toggle rows to the patched layout ----
            toggles_warm = []
            for b, bd in enumerate(self._buckets):
                shape = tuple(bd.idx.shape)
                src = carry[b] if b < len(carry) else None
                if src is None or cache["toggles"][src].shape != shape:
                    toggles_warm.append(jnp.zeros(shape, jnp.float32))
                    srvs = np.asarray(bd.servers)
                    stale[srvs[srvs < k]] = True   # skip sharded padding rows
                else:
                    toggles_warm.append(jnp.asarray(cache["toggles"][src]))
            warm = (jnp.asarray(cache["cur"]), tuple(toggles_warm),
                    jnp.asarray(stale))

        self.last_repaired_assignment = assign.copy()
        assignment, member, moves, trace = self._sweep(
            assign, profile, max_moves, exchange_samples,
            jax.random.PRNGKey(self.seed), warm=warm)
        host = dict(zip(RERUN_COUNT_NAMES, (
            int(departed.sum()), int(arrived.sum()), int(displaced.sum()),
            rebuilds)))
        self.last_counts.update(host)
        tracing.event("hfel.rerun.count", **host)
        if verify:
            cold = FastAssociationEngine(
                sc_new, kind=self.kind, permission=self.permission,
                min_residual_group=self.min_residual, seed=self.seed,
                rel_tol=self.rel_tol, profile=profile, compact=self.compact,
                shards=self.shards, ra_backend=self.ra_backend)
            ref = cold.run(assignment=self.last_repaired_assignment,
                           max_moves=max_moves,
                           exchange_samples=exchange_samples, finalize=False)
            if not np.array_equal(assignment, ref):
                raise AssertionError(
                    "incremental warm start diverged from the cold rebuild: "
                    f"{int((assignment != ref).sum())} "
                    "device placements differ")
        if not finalize:
            return assignment.copy()
        return self._finalize(assignment, member, moves, trace)

    @property
    def stable_assignment(self) -> np.ndarray | None:
        """The most recent stable-point assignment (parked slots included),
        readable after any ``run``/``run_tiered``/``rerun_incremental``
        without holding on to result objects — the handoff surface for
        external drivers polling the engine between re-solves. ``None``
        before the first run."""
        if self._warm_cache is None:
            return None
        return np.asarray(self._warm_cache["assignment"]).copy()

    def _member_of(self, assignment: np.ndarray) -> np.ndarray:
        return _dense_member(np.asarray(assignment), self._active,
                             self.sc.n_servers)

    def _sweep(self, assignment: np.ndarray, profile: str, max_moves: int,
               exchange_samples: int, key, rel_tol: float | None = None,
               warm=None):
        """One profile's adjustment loop; returns (assignment, dense member,
        n_moves, trace), stashes the cache dump in ``last_state`` and the
        work counters in ``last_counts``."""
        rel_tol = self.rel_tol if rel_tol is None else rel_tol
        assignment = np.asarray(assignment)
        n, k = self.sc.n_devices, self.sc.n_servers
        if self.compact:
            # an out-of-reach assignment has no slot in compacted space: the
            # device would silently vanish from its group and the sweep's
            # slot_of gather would clamp to an unrelated device's toggle
            # cost, so reject it loudly (the dense path merely prices the
            # unreachable placement like the reference engine does)
            unreachable = self._active & ~self.avail[assignment, np.arange(n)]
            if unreachable.any():
                bad = np.flatnonzero(unreachable)[:8]
                raise ValueError(
                    "compact sweep requires every device assigned within "
                    f"reach; devices {bad.tolist()} are not (e.g. device "
                    f"{bad[0]} -> server {assignment[bad[0]]})")
        if self.cap is not None:
            # transfers are cap-gated and exchanges cap-neutral, so a sweep
            # preserves feasibility — but only if it STARTS feasible; an
            # over-cap explicit assignment would stay over-cap forever
            load = np.bincount(assignment[self._active], minlength=k)
            over = np.flatnonzero(load > self.cap)
            if over.size:
                raise ValueError(
                    f"assignment exceeds max_devices at server(s) "
                    f"{over.tolist()[:8]} (load "
                    f"{load[over].tolist()[:8]} > cap "
                    f"{self.cap[over].tolist()[:8]})")
        with tracing.span("hfel.init_assign"):
            member0 = jnp.asarray(self._member_of(assignment))
            assign0 = jnp.asarray(assignment, jnp.int32)
        if self._mesh is None:
            init = partial(_init_cache, kind=self.kind, profile=profile,
                           ra_backend=self.ra_backend)
            loop = partial(_run_device, kind=self.kind, profile=profile,
                           permission=self.permission,
                           min_residual=self.min_residual,
                           max_moves=max_moves,
                           exchange_samples=exchange_samples,
                           ra_backend=self.ra_backend)
        else:
            init = _sharded_init(self._mesh, len(self._buckets),
                                 warm is not None, kind=self.kind,
                                 profile=profile, ra_backend=self.ra_backend)
            loop = _sharded_runner(
                self._mesh, len(self._buckets), kind=self.kind,
                profile=profile, permission=self.permission,
                min_residual=self.min_residual, max_moves=max_moves,
                exchange_samples=exchange_samples,
                ra_backend=self.ra_backend)
        kept, stale = (None, None) if warm is None else (warm[:2], warm[2])
        with tracing.span("hfel.sweep.init"):
            cache = tracing.ready(init(member0, self._buckets,
                                       self.cloud_const, kept, stale))
        with tracing.span("hfel.sweep.loop"):
            out = tracing.ready(loop(
                member0, assign0, key, *cache, self._buckets,
                self._ex_bucket, self._slot_of, self._bucket_of,
                self._row_of, self.cloud_const, self._cap,
                jnp.float32(rel_tol)))
        with tracing.span("hfel.readback"):
            # one transfer for every output; the move trace is cut on the
            # host, so no program is compiled per move count
            member, assign, cur, toggles, moves, trace, counts = \
                jax.device_get(out)
            self.last_state = {"member": member, "cur_cost": cur}
            if self.compact == "bucketed":
                self.last_state.update(
                    toggle_cost_buckets=list(toggles),
                    reach_buckets=self.reach_buckets)
            elif self.compact:
                r = self.reach
                self.last_state.update(
                    member_compact=(member[np.arange(k)[:, None], r.idx]
                                    & r.valid),
                    toggle_cost_compact=toggles[0], reach=r)
            else:
                self.last_state.update(toggle_cost=toggles[0])
            moves = int(moves)
            self.last_moves = moves
            self.last_counts = dict(zip(COUNT_NAMES, counts.tolist()))
            trace = [float(x) for x in trace[:moves + 1].astype(np.float64)]
            assign = assign.astype(np.int64)
            # stable-point cache for rerun_incremental: everything a warm
            # start needs to skip the full toggle-cache init after a
            # scenario delta
            self._warm_cache = {"assignment": assign.copy(), "cur": cur,
                                "toggles": list(toggles), "profile": profile}
        tracing.event("hfel.count", **self.last_counts)
        return assign, member, moves, trace

    def _finalize(self, assignment, member, moves, trace) -> AssociationResult:
        with tracing.span("hfel.finalize"):
            k = self.sc.n_servers
            masks = np.asarray(member)
            sols = self._eval_solver.solve_batch(np.arange(k), masks)
            jmasks = jnp.asarray(masks)
            f = np.asarray(jnp.sum(jnp.where(jmasks, sols.f, 0.0), axis=0))
            beta = np.asarray(jnp.sum(jnp.where(jmasks, sols.beta, 0.0),
                                      axis=0))
            server_cost = np.asarray(sols.cost)
            total = float(np.sum(
                server_cost + np.where(masks.any(axis=1),
                                       np.asarray(self.cloud_const), 0.0)))
            # true (15)-(17) costs are over the active population only:
            # inactive devices hold no resources (f = beta = 0 in the masked
            # sums above) and must not enter the per-device energy/delay
            # terms
            e, t, c = _true_cost_terms(self.sc, self._active, assignment, f,
                                       beta)
            return AssociationResult(
                assignment=assignment.copy(), f=f, beta=beta,
                server_cost=server_cost, total_cost=total,
                true_energy=float(e), true_delay=float(t), true_cost=float(c),
                n_adjustments=moves, n_rounds=moves, cost_trace=trace)
