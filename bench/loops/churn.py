"""Traffic kind ``churn``: a closed loop of warm re-solves after mobility
and churn.

Set-up builds the configuration's hexagonal deployment
(``benchlib.hexgrid``), one engine, its cold finalized ``run("nearest")``
and ``warmup_ticks`` warm re-solves. Each request is the next tick of the
chain (generated, untimed), then ``diff_scenarios`` of the previous and the
next deployment plus a finalized ``rerun_incremental`` (timed): the
assignment with its (f, beta) and eq.-17 cost. Each tick is handed over
once the previous re-solve has returned. The chain is drawn from the
traffic's ``chain_seed``, so every run serves the same ticks; the run's
seed picks only the sample of answers that is checked.

Each record holds ``t``, ``moves``, the engine's ``last_counts`` and, in a
traced run, the ``hfel.*`` span durations of the request (the loop turns
the program's tracing on itself; a program without span durations leaves
them out)."""

from __future__ import annotations

import numpy as np

from benchlib import reference as ref
from benchlib.hexgrid import Chain
from benchlib.sample import Sample, engine_kwargs, sub_seed
from benchlib.window import span


def _take_durations():
    from repro.utils import tracing

    take = getattr(tracing, "take_durations", None)
    return take() if take is not None else None


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
        from repro.core.assoc_fast import FastAssociationEngine
        from repro.utils import tracing

        tracing.enable(self.traced)
        self.chain = Chain(self.cfg, self.traffic)
        self.sc = self.chain.base
        self.ticks = 0
        self.eng = FastAssociationEngine(
            self.sc, seed=self.cfg["engine"]["seed"],
            **engine_kwargs(self.cfg))
        self.eng.run("nearest", exchange_samples=self.ex)
        for _ in range(self.traffic["warmup_ticks"]):
            self.serve(self.next_request(-1))
        _take_durations()

    def next_request(self, i: int):
        self.ticks += 1
        return self.sc, self.chain.tick(self.sc, self.ticks)

    def serve(self, req):
        from repro.core.scenario import diff_scenarios

        prev, nxt = req
        with span("bench.solve", self.traced):
            delta = diff_scenarios(prev, nxt)
            res = self.eng.rerun_incremental(nxt, delta,
                                             exchange_samples=self.ex)
        self.sc = nxt
        rec = {"moves": self.eng.last_moves,
               "counts": dict(self.eng.last_counts)}
        spans = _take_durations() if self.traced else None
        if spans:
            rec["spans"] = spans
        return res, rec

    def keep(self, i, req, answer, rec) -> None:
        self.sample.offer(i, (req[1], answer), rec["t"])

    def check(self, control=None) -> tuple[dict, dict | None]:
        """Reach of every device, placement gaps of ``check_devices``
        seeded active devices, and the allocation numbers over every group,
        of each sampled answer. With ``control`` (a dtype), the control's
        numbers on the same answers too."""
        e = self.cfg["engine"]
        nums = {"unreachable": 0, "gap": 0.0, "ra_gap": -np.inf,
                "ra_infeasible": -np.inf, "cost_gap": 0.0}
        ctrl = dict(nums) if control is not None else None
        rng = np.random.default_rng(sub_seed(self.seed, 11))
        checked = placements = 0
        for sc, res in self.sample.items():
            model = ref.Model(sc)
            bad = ref.unreachable(model, res.assignment)
            act = np.flatnonzero(model.active)
            devices = rng.choice(act, min(self.traffic["check_devices"],
                                          act.size), replace=False)
            g, cg = ref.placement_gaps(model, res.assignment, devices,
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
        """The engine's state is the loop's own; nothing to hand back."""
        from repro.utils import tracing

        tracing.enable(False)
