"""Traffic kind ``cold_solve``: a closed loop of cold solves.

Each request is a deployment of the configuration (generated, untimed),
then a new ``FastAssociationEngine`` and a finalized ``run("nearest")``
(timed): the assignment with its (f, beta) and eq.-17 cost. A seeded sample
of the answers, plus the slowest, is checked against the plain reference
after the window.

Traffic parameters: ``pool`` deployments drawn from ``pool_seed``, served
in an order drawn from the run's seed and cycled, so that every seed gets
the same work in another order; ``warmup_requests`` of them, the first of
the pool, solved in set-up; ``warm_moves``, the longest move trace whose
read-back is compiled in set-up."""

from __future__ import annotations

import time

import numpy as np

from benchlib import reference as ref
from benchlib.sample import Sample, engine_kwargs, sub_seed, warm_trace_reads
from benchlib.scenarios import make_deployment
from benchlib.window import span


class Loop:
    def __init__(self, cell, seed: int, *, traced: bool = False):
        self.cfg, self.traffic = cell.config, cell.traffic
        self.limits = cell.limits
        self.seed, self.traced = seed, traced
        self.ex = self.cfg["engine"]["exchange_samples"]
        self.sample = Sample(self.limits["sample"]["requests"],
                             sub_seed(seed, 9))
        self.check_info = ""

    def setup(self) -> None:
        t = self.traffic
        self.order = np.random.default_rng(sub_seed(self.seed, 4)) \
            .permutation(t["pool"])
        warm_trace_reads(t["warm_moves"])
        for j in range(t["warmup_requests"]):
            self.serve(self.deployment(j))

    def deployment(self, j: int):
        return make_deployment(self.cfg,
                               sub_seed(self.traffic["pool_seed"], j))

    def next_request(self, i: int):
        return self.deployment(int(self.order[i % self.order.size]))

    def serve(self, sc):
        from repro.core.assoc_fast import FastAssociationEngine

        t0 = time.perf_counter()
        with span("bench.build", self.traced):
            eng = FastAssociationEngine(sc, seed=sub_seed(self.seed, 2),
                                        **engine_kwargs(self.cfg))
        t1 = time.perf_counter()
        with span("bench.solve", self.traced):
            res = eng.run("nearest", exchange_samples=self.ex)
        return res, {"build_s": t1 - t0, "moves": eng.last_moves}

    def keep(self, i, sc, answer, rec) -> None:
        self.sample.offer(i, (sc, answer), rec["t"])

    def check(self, control=None) -> tuple[dict, dict | None]:
        """Reach and placement gaps of every device, and the allocation
        numbers, of each sampled answer. With ``control`` (a dtype), the
        control's numbers on the same answers too."""
        e = self.cfg["engine"]
        nums = {"unreachable": 0, "gap": 0.0, "ra_gap": -np.inf,
                "ra_infeasible": -np.inf, "cost_gap": 0.0}
        ctrl = dict(nums) if control is not None else None
        checked = placements = 0
        for sc, res in self.sample.items():
            model = ref.Model(sc)
            bad = ref.unreachable(model, res.assignment)
            g, cg = ref.placement_gaps(model, res.assignment,
                                       np.flatnonzero(model.active),
                                       min_residual=e["min_residual_group"],
                                       pick_dtype=control)
            a, ac = ref.allocation_checks(model, res.assignment, res.f,
                                          res.beta, res.true_cost,
                                          ctrl_dtype=control)
            checked += 1
            placements += g.size
            for out, gaps, alloc in ((nums, g, a), (ctrl, cg, ac)):
                if out is None:
                    continue
                out["unreachable"] += bad
                out["gap"] = max(out["gap"], float(np.max(gaps, initial=0.0)))
                for key, v in alloc.items():
                    out[key] = max(out[key], v)
        self.check_info = (f"checked {checked} answers, {placements} device "
                           f"placements")
        return nums, ctrl

    def release(self) -> None:
        """Nothing of the program's state outlives a request."""
