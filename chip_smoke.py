#!/usr/bin/env python
"""Bring-up check of the live HFEL loop on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # the sharded sweep on four chips

One chip runs these phases in order, in this one process:

1. device check: the first JAX device must be a TPU. There is no CPU path;
   on any other platform the script exits non-zero.
2. scenario and data: ``make_large_scenario(20_000, 200)`` and a 200-client
   MNIST-shaped dataset (784 features, 60,000 samples), both from ``--seed``.
3. live run: ``run_live`` with the incremental-warm policy over 3 rounds:
   a cold round-0 association, then two warm re-solves with sampled
   exchanges, each checked against a cold rebuild (``verify=True``), with
   candidate groups priced by the Pallas golden-section kernel, while the
   784-128-10 MLP trains on the association it produces.
4. checks: every stable assignment keeps each active device on a server it
   can reach; the round-0 eq.-17 cost beats the nearest-server assignment;
   the train loss is finite; test accuracy beats chance.
5. kernel check: at G=4096 groups the lowered Pallas-backed solve holds a
   ``tpu_custom_call`` (the kernel was compiled by Mosaic, not
   interpreted), and its costs agree with the XLA solver's at rtol 2e-4
   in 99% of the groups, its deadlines in the median group (see
   ``kernel_phase`` for why not in every group).

``--chips 4`` runs only the sharded sweep: a cold solve and one churn tick
re-solved warm, with ``shards=4`` and with ``shards=None``, on the same
scenario. ``assignment``, ``n_adjustments`` and ``cost_trace`` must be
bit-identical.

Every failed check exits non-zero. The last line of standard output is the
JSON object ``{"ok": true, "device": {...}}``, printed only when all passed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_DEVICES, N_SERVERS = 20_000, 200
N_CLIENTS, N_SAMPLES, DIM = 200, 60_000, 784
ROUNDS = 3
EXCHANGE_SAMPLES = 64
PROFILE, REL_TOL = "coarse", 1e-3          # run_live's sweep accuracy
KERNEL_G, KERNEL_R = 4096, 64
KERNEL_RTOL = 2e-4                         # the kernel's pinned parity
KERNEL_CORE_SHARE = 0.99                   # groups that must hold the pin


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def device_check(chips: int) -> dict:
    import jax

    devs = jax.devices()
    dev = devs[0]
    require(dev.platform == "tpu",
            f"needs a TPU; JAX found platform {dev.platform!r}")
    require(len(devs) >= chips,
            f"needs {chips} chips; JAX found {len(devs)}")
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    return info


def build_inputs(seed: int):
    from repro.core.scenario import make_large_scenario
    from repro.data import make_mnist_like

    t0 = time.perf_counter()
    sc = make_large_scenario(N_DEVICES, N_SERVERS, seed=seed)
    ds = make_mnist_like(N_CLIENTS, dim=DIM, samples_total=N_SAMPLES,
                         seed=seed)
    log(f"[inputs] N={sc.n_devices} K={sc.n_servers} "
        f"clients={ds.n_clients} samples={int(ds.client_sizes.sum())} "
        f"dim={ds.dim} in {time.perf_counter() - t0:.3f} s")
    return sc, ds


def live_phase(sc, ds, seed: int):
    from repro.fl import run_live

    t0 = time.perf_counter()
    hist = run_live(sc, ds, policy="incremental-warm", rounds=ROUNDS,
                    resolve_every=1, seed=seed, train_seed=seed, model="mlp",
                    profile=PROFILE, rel_tol=REL_TOL, compact="bucketed",
                    exchange_samples=EXCHANGE_SAMPLES, ra_backend="pallas",
                    verify=True)
    wall = time.perf_counter() - t0
    tr = hist.train
    log("[live] round swap moves assoc_s(warm rounds incl. verify) "
        "eq17_cost train_loss test_acc")
    for r in range(hist.rounds):
        i = tr.eval_rounds.index(r)
        log(f"[live] {r} {hist.swapped[r]} {hist.moves[r]} "
            f"{hist.assoc_seconds[r]:.3f} {hist.system_cost[r]:.6g} "
            f"{tr.train_loss[i]:.6g} {tr.test_acc[i]:.4f}")
    log(f"[live] wall {wall:.3f} s")
    return hist


def live_checks(sc, ds, hist, seed: int) -> None:
    from repro.core.assoc_fast import assignment_true_cost
    from repro.core.edge_association import initial_assignment
    from repro.fl import churn_tick

    # replay the run's scenario trajectory to check each swap where it ran
    scs = [sc]
    for r in range(1, hist.rounds):
        scs.append(churn_tick(scs[-1], seed=seed, r=r)[0])
    for r, assign in zip(hist.swap_rounds, hist.swap_assignments):
        act = np.flatnonzero(scs[r].active_mask)
        require(int(act.size) == hist.n_active[r],
                f"replayed round {r} has {act.size} active devices, the run "
                f"had {hist.n_active[r]}")
        require(np.asarray(scs[r].eff_avail)[assign[act], act].all(),
                f"round {r}: an active device sits on a server it cannot "
                "reach")
    nearest = initial_assignment(sc, np.asarray(sc.eff_avail),
                                 np.random.default_rng(seed), "nearest")
    _, _, c_nearest = assignment_true_cost(sc, nearest, seed=seed)
    log(f"[check] round-0 eq17 cost {hist.system_cost[0]:.6g} vs nearest "
        f"{c_nearest:.6g}")
    require(hist.system_cost[0] < c_nearest,
            "round-0 association does not beat the nearest-server cost")
    losses = np.asarray(hist.train.train_loss, np.float64)
    require(np.isfinite(losses).all(), f"train loss not finite: {losses}")
    acc = hist.train.test_acc[-1]
    require(acc > 1.0 / ds.n_classes,
            f"test accuracy {acc:.4f} is not above chance")
    log("[check] stable assignments reachable, cost, loss, accuracy: ok")


def kernel_phase(sc, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import resource_allocation as ra
    from repro.core.edge_association import GroupSolver

    # G candidate groups as the sweep forms them: each a random subset of
    # one server's reachable devices, priced at that server
    g, r = KERNEL_G, KERNEL_R
    rng = np.random.default_rng(seed)
    avail = np.asarray(sc.avail)
    srv = rng.integers(0, sc.n_servers, g)
    idx = np.zeros((g, r), np.int64)
    size = np.zeros(g, np.int64)
    for i, s in enumerate(srv):
        reach = np.flatnonzero(avail[s])
        m = min(reach.size, r)
        idx[i, :m] = rng.choice(reach, m, replace=False)
        size[i] = rng.integers(1, m + 1)
    consts = GroupSolver(sc, "fast", seed=seed).consts
    srv, idx = jnp.asarray(srv), jnp.asarray(idx)
    c = jax.tree.map(lambda x: x[srv[:, None], idx] if x.ndim == 2
                     else x[srv], consts)
    masks = jnp.asarray(np.arange(r) < size[:, None])
    for profile in ("default", "coarse"):
        iters = ra.SCREEN_PROFILES[profile]
        sols = {}
        for backend in ("pallas", "xla"):
            fn = jax.jit(lambda c_, m_, b=backend, it=iters:
                         ra.solve_fixed_point_batched(c_, m_, backend=b,
                                                      **it))
            lowered = fn.lower(c, masks)
            if backend == "pallas":
                require("tpu_custom_call" in lowered.as_text(),
                        f"{profile}: the Pallas solve holds no "
                        "tpu_custom_call (kernel interpreted?)")
            compiled = lowered.compile()
            jax.block_until_ready(compiled(c, masks))        # warm-up
            t0 = time.perf_counter()
            sols[backend] = jax.block_until_ready(compiled(c, masks))
            log(f"[kernel] {profile} {backend} G={g} R={r} "
                f"{(time.perf_counter() - t0) * 1e3:.3f} ms")
        # Per group the two backends need not agree: the cost along the
        # deadline path is not unimodal for some groups, so an ulp-level
        # difference can send golden section to another local minimum (on
        # the chip the XLA solver moves as far under a 2-ulp change of its
        # own inputs), and where the cost is flat in the deadline the
        # deadline is not determined at all. So the pin must hold for the
        # cost, which the sweep consumes, in KERNEL_CORE_SHARE of the
        # groups, and for the deadline in the median group.
        for field, share in (("cost", KERNEL_CORE_SHARE), ("deadline", 0.5)):
            got = np.asarray(getattr(sols["pallas"], field), np.float64)
            want = np.asarray(getattr(sols["xla"], field), np.float64)
            rel = np.abs(got - want) / np.maximum(np.abs(want), 1e-30)
            within = float(np.mean(rel <= KERNEL_RTOL))
            log(f"[kernel] {profile} {field} rel err: max {rel.max():.3e} "
                f"p99 {np.quantile(rel, 0.99):.3e} "
                f"median {np.median(rel):.3e}; share within {KERNEL_RTOL}: "
                f"{within:.4f}")
            require(np.isfinite(got).all(), f"{profile} {field}: not finite")
            require(within >= share,
                    f"{profile} {field}: only {within:.4f} of the groups "
                    f"agree within {KERNEL_RTOL} (needs {share})")


def sharded_phase(seed: int, chips: int) -> None:
    import jax

    from repro.core.assoc_fast import FastAssociationEngine
    from repro.core.scenario import make_large_scenario
    from repro.fl import churn_tick

    sc = make_large_scenario(N_DEVICES, N_SERVERS, seed=seed)
    sc1, delta = churn_tick(sc, seed=seed, r=1)
    runs = {}
    for shards in (chips, None):
        eng = FastAssociationEngine(sc, kind="fast", seed=seed,
                                    profile=PROFILE, rel_tol=REL_TOL,
                                    compact="bucketed", shards=shards)
        t0 = time.perf_counter()
        cold = eng.run("nearest", exchange_samples=EXCHANGE_SAMPLES)
        t1 = time.perf_counter()
        warm = eng.rerun_incremental(sc1, delta,
                                     exchange_samples=EXCHANGE_SAMPLES)
        t2 = time.perf_counter()
        log(f"[sharded] shards={shards} cold {t1 - t0:.3f} s "
            f"{cold.n_adjustments} moves cost {cold.total_cost:.6g}; "
            f"warm {t2 - t1:.3f} s {warm.n_adjustments} moves "
            f"cost {warm.total_cost:.6g}")
        if shards:
            # the engine holds its bucket rows, one slice per chip
            used = [d.memory_stats()["bytes_in_use"]
                    for d in jax.devices()[:chips]]
            log(f"[sharded] bytes_in_use per device: {used}")
            require(all(u > 0 for u in used),
                    "a chip of the mesh holds no bytes")
        runs[shards] = (cold, warm)
    for i, leg in enumerate(("cold", "warm")):
        a, b = runs[chips][i], runs[None][i]
        same = (np.array_equal(a.assignment, b.assignment),
                a.n_adjustments == b.n_adjustments,
                a.cost_trace == b.cost_trace)
        log(f"[sharded] {leg}: assignment/n_adjustments/cost_trace "
            f"identical: {same}")
        require(all(same), f"{leg}: shards={chips} differs from shards=None")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    device = device_check(args.chips)
    from repro.utils.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")

    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(args.seed, args.chips)
        log(f"[phase] sharded {time.perf_counter() - t0:.3f} s")
    else:
        sc, ds = build_inputs(args.seed)
        t1 = time.perf_counter()
        hist = live_phase(sc, ds, args.seed)
        t2 = time.perf_counter()
        live_checks(sc, ds, hist, args.seed)
        t3 = time.perf_counter()
        kernel_phase(sc, args.seed)
        t4 = time.perf_counter()
        log(f"[phase] inputs {t1 - t0:.3f} s, live {t2 - t1:.3f} s, "
            f"checks {t3 - t2:.3f} s, kernel {t4 - t3:.3f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
