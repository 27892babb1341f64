"""CPU tests of the ``churn`` traffic kind and its ``metro20k`` deployment:
the hexagonal layout has the geometry the configuration states, warm
re-solves along the chain land where cold rebuilds do and keep their reach
map's width inside its headroom, the per-layer readers read what the loop
records, and faults planted in the warm path read ``correct`` false.

The faults run at 240 devices on 12 sites, with the cell's own traffic and
check sample: there, as in the cell, every tick brings arrivals and reach
changes, and the 64 checked devices are a sample, not every device."""

import dataclasses
import json
import math
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from benchlib import harness, spec  # noqa: E402
from benchlib import reference as ref  # noqa: E402
from benchlib.hexgrid import Chain, make_hex_deployment  # noqa: E402

CFG = json.loads((BENCH / "configs" / "metro20k.json").read_text())
TRAFFIC = json.loads((BENCH / "traffic" / "churn.json").read_text())


def test_hex_deployment_has_the_configured_geometry():
    sc, hull, indoor = make_hex_deployment(CFG, TRAFFIC["chain_seed"])
    x0, x1, y0, y1 = hull
    isd = CFG["layout"]["isd_m"]
    area_km2 = (x1 - x0) * (y1 - y0) / 1e6
    assert sc.n_servers == 200 and sc.n_devices == 20_000
    assert area_km2 == pytest.approx(200 * math.sqrt(3) / 2 * isd ** 2 / 1e6)
    assert area_km2 == pytest.approx(43.30, abs=0.01)
    active = sc.active_mask
    assert active.mean() == pytest.approx(0.95)
    assert indoor.mean() == pytest.approx(0.8, abs=0.01)
    eff = np.asarray(sc.eff_avail)
    # about pi r^2 / cell area = 3.6 sites a device, fewer at the hull's edge
    assert 3.0 < eff[:, active].sum(axis=0).mean() < 3.7
    counts = eff.sum(axis=1)
    assert 280 < np.median(counts) < 380
    from repro.core.scenario import reach_index_map

    assert reach_index_map(sc.avail, active=active).r_max == 512


def _small(n_servers=12, n_devices=240):
    cfg = dict(CFG, n_devices=n_devices, n_servers=n_servers,
               layout=dict(CFG["layout"], columns=4))
    return Chain(cfg, TRAFFIC)


def test_warm_resolves_match_cold_rebuilds_along_the_chain():
    """Ten chained ticks, each re-solved warm with the cold-rebuild parity
    gate on: no tick leaves the flat map's headroom. Then one site is put in
    reach of every device, past the headroom: the map is rebuilt once, and
    the warm answer still matches the cold one."""
    from repro.core.assoc_fast import FastAssociationEngine
    from repro.core.scenario import diff_scenarios

    chain = _small()
    sc = chain.base
    eng = FastAssociationEngine(sc, kind="fast", profile="coarse", seed=3,
                                rel_tol=1e-3, compact=True)
    eng.run("nearest", exchange_samples=16, finalize=False)
    width = eng.reach.r_max
    assert width < sc.n_devices
    for j in range(1, 11):
        nxt = chain.tick(sc, j)
        eng.rerun_incremental(nxt, diff_scenarios(sc, nxt),
                              exchange_samples=16, verify=True,
                              finalize=False)
        assert eng.last_counts["reach_rebuilds"] == 0
        assert eng.last_counts["stale_rows"] == sc.n_servers
        assert eng.reach.r_max == width
        sc = nxt
    avail = np.array(sc.avail, copy=True)
    avail[0] = True
    wide = dataclasses.replace(sc, avail=avail)
    eng.rerun_incremental(wide, diff_scenarios(sc, wide),
                          exchange_samples=16, verify=True, finalize=False)
    assert eng.last_counts["reach_rebuilds"] == 1
    assert eng.reach.r_max > width


def test_chain_is_the_same_in_every_run():
    a, b = _small(), _small()
    np.testing.assert_array_equal(a.base.dev_xy, b.base.dev_xy)
    ta, tb = a.tick(a.base, 4), b.tick(b.base, 4)
    np.testing.assert_array_equal(ta.dev_xy, tb.dev_xy)
    np.testing.assert_array_equal(ta.active_mask, tb.active_mask)
    moved = np.linalg.norm(ta.dev_xy - a.base.dev_xy, axis=1)
    assert moved.max() <= TRAFFIC["outdoor_mps"] * TRAFFIC["tick_s"] + 1e-9


def _reader(name):
    return spec.metric_reader(name)


def test_churn_readers_read_the_records():
    recs = [{"t": 1.0, "moves": m, "counts": {"stale_rows": s},
             "spans": {"hfel.sweep.init": [i], "hfel.rerun.patch": [0.001],
                       "hfel.rerun.repair": [r]}}
            for m, s, i, r in ((4, 200, 0.5, 0.002), (6, 198, 0.7, 0.004),
                               (8, 190, 0.6, 0.003))]
    ctx = {"records": recs}
    assert _reader("init_ms.churn")(ctx) == pytest.approx(600.0)
    assert _reader("repair_ms.churn")(ctx) == pytest.approx(4.0)
    assert _reader("stale_rows.churn")(ctx) == pytest.approx(196.0)
    assert _reader("moves_per_req.churn")(ctx) == pytest.approx(6.0)
    # a program that keeps no span durations: the span readers read nothing
    bare = {"records": [{"t": 1.0, "moves": 3, "counts": {}}]}
    assert _reader("init_ms.churn")(bare) is None
    assert _reader("repair_ms.churn")(bare) is None
    assert _reader("stale_rows.churn")(bare) is None


# ---- faults planted in the warm path --------------------------------------

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SMALL = {"n_devices": 240, "n_servers": 12}


def small_cell():
    cell = spec.load_cell("metro20k.churn")
    return dataclasses.replace(
        cell, config=dict(cell.config, **SMALL,
                          layout=dict(cell.config["layout"], columns=4)),
        traffic=dict(cell.traffic, warmup_ticks=1))


def verdict(capsys, seed=2**31 + 99):
    harness.main(["--workload", "metro20k.churn", "--seed", str(seed),
                  "--seconds", "3", "--trace", "0"],
                 cell=small_cell(), device=CPU)
    out, _ = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1])


def previous_answer(orig):
    """Each request answers with the previous tick's result: the previous
    assignment, unrepaired for the tick's departures and arrivals."""
    last = {}

    def rerun(self, *a, **kw):
        res = orig(self, *a, **kw)
        out = last.get("res", res)
        last["res"] = res
        return out
    return rerun


def unmoved(orig):
    """The repaired start returned without the descent to a stable point."""
    def rerun(self, *a, **kw):
        return orig(self, *a, **dict(kw, max_moves=0))
    return rerun


@pytest.mark.parametrize("fault", [previous_answer, unmoved])
def test_a_broken_warm_path_is_not_correct(fault, capsys, monkeypatch):
    from repro.core.assoc_fast import FastAssociationEngine

    orig = FastAssociationEngine.rerun_incremental
    monkeypatch.setattr(FastAssociationEngine, "rerun_incremental",
                        fault(orig))
    res = verdict(capsys)
    assert res["correct"] is False, res["checks"]


def test_an_unrepaired_start_is_refused_by_the_program(capsys, monkeypatch):
    """With the host repair skipped, devices the tick took out of their
    server's reach keep that server: the compact sweep refuses the start
    before any answer exists."""
    import repro.core.assoc_fast as af

    def unrepaired(sc_new, prev_assign, old_active):
        none = np.zeros(sc_new.n_devices, bool)
        return np.asarray(prev_assign).copy(), none, none, none

    monkeypatch.setattr(af, "repair_assignment", unrepaired)
    with pytest.raises(ValueError, match="within reach"):
        verdict(capsys)


def test_a_dropped_stale_mark_costs_moves_not_the_stable_point():
    """One server's stale mark dropped at every tick, the one whose group
    the repair changed most, so its row keeps the previous tick's costs.
    The descent still ends where no device gains from a transfer by more
    than the cell's ``gap`` limit, over every active device and not a
    sample: the moves refresh the rows they touch. So ``correct`` has
    nothing to see, however many devices it checked; the fault shows in
    ``stale_rows.churn`` instead."""
    import jax.numpy as jnp

    from repro.core.assoc_fast import FastAssociationEngine
    from repro.core.scenario import diff_scenarios
    from benchlib.sample import engine_kwargs

    cell = small_cell()
    orig = FastAssociationEngine._sweep

    def sweep(self, assignment, *a, warm=None, **kw):
        if warm is not None:
            prev = np.asarray(self._warm_cache["assignment"])
            act, k = self._active, self.sc.n_servers
            new = np.asarray(assignment)
            moved = (new != prev) & act
            change = (np.bincount(new[moved], minlength=k)
                      + np.bincount(prev[moved], minlength=k)
                      + np.abs(np.bincount(new[act], minlength=k)
                               - np.bincount(prev[act], minlength=k)))
            stale = np.asarray(warm[2]).copy()
            stale[int(np.argmax(change))] = False
            warm = (warm[0], warm[1], jnp.asarray(stale))
        return orig(self, assignment, *a, warm=warm, **kw)

    chain = Chain(cell.config, cell.traffic)
    sc = chain.base
    eng = FastAssociationEngine(sc, seed=cell.config["engine"]["seed"],
                                **engine_kwargs(cell.config))
    eng._sweep = sweep.__get__(eng)
    eng.run("nearest", exchange_samples=64)
    for j in range(1, 4):
        nxt = chain.tick(sc, j)
        res = eng.rerun_incremental(nxt, diff_scenarios(sc, nxt),
                                    exchange_samples=64)
        sc = nxt
        assert eng.last_counts["stale_rows"] == sc.n_servers - 1
        model = ref.Model(sc)
        gaps, _ = ref.placement_gaps(
            model, res.assignment, np.flatnonzero(model.active),
            min_residual=cell.config["engine"]["min_residual_group"])
        assert gaps.max() <= cell.limits["limits"]["gap"]
