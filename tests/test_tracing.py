"""Host spans and work counters of the association request
(``repro.utils.tracing``): tracing on changes no answer, tracing off costs
neither an annotation nor a device wait, the spans land nested in a
profiler trace, and the counters match the work the sweep's shapes imply.

Four-shard cases need ``XLA_FLAGS=--xla_force_host_platform_device_count``
(exported by ``scripts/tier1.sh``) and skip on a single-device run."""

import glob

import jax
import numpy as np
import pytest

import repro.core.assoc_fast as assoc_fast
from repro.core import make_scenario
from repro.core.assoc_fast import (COUNT_NAMES, RERUN_COUNT_NAMES,
                                   FastAssociationEngine)
from repro.core.scenario import make_large_scenario, perturb_scenario
from repro.fl.live import LiveHFELRunner
from repro.utils import tracing

CHURN = dict(drift_m=60.0, move_frac=0.1, flip_frac=0.05, depart_frac=0.05)
SPACES = {False: lambda: make_scenario(14, 3, seed=1),
          True: lambda: make_large_scenario(40, 5, seed=2),
          "bucketed": lambda: make_large_scenario(40, 5, seed=3)}
SPACE_IDS = ["dense", "flat", "bucketed"]
SWEEP_SPANS = ("hfel.init_assign", "hfel.sweep.init", "hfel.sweep.loop",
               "hfel.readback")
BUILD_SPANS = ("hfel.build.solver", "hfel.build.reach", "hfel.build.space")


@pytest.fixture
def traced():
    """Tracing on for the test, off again after it whatever happens."""
    tracing.enable(True)
    yield
    tracing.enable(False)


def _engine(compact, shards=None):
    return FastAssociationEngine(SPACES[compact](), kind="fast", seed=0,
                                 profile="coarse", rel_tol=1e-4,
                                 compact=compact, shards=shards)


def _cold_then_warm(compact, exchange_samples, shards=None):
    """A finalized cold solve, then a verified warm re-solve after churn."""
    eng = _engine(compact, shards)
    cold = eng.run("nearest", exchange_samples=exchange_samples)
    sc2, delta = perturb_scenario(eng.sc, seed=1, **CHURN)
    warm = eng.rerun_incremental(sc2, delta,
                                 exchange_samples=exchange_samples,
                                 verify=True)
    return eng, cold, warm


def _answer(res):
    return (res.assignment.tolist(), res.n_adjustments, res.cost_trace)


@pytest.mark.parametrize("shards", [None, 4], ids=["single", "p4"])
@pytest.mark.parametrize("exchange_samples", [0, 64])
@pytest.mark.parametrize("compact", list(SPACES), ids=SPACE_IDS)
def test_tracing_changes_no_answer(compact, exchange_samples, shards):
    if shards is not None and shards > len(jax.devices()):
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count"
                    " (scripts/tier1.sh exports it)")
    _, cold_off, warm_off = _cold_then_warm(compact, exchange_samples, shards)
    tracing.enable(True)
    try:
        _, cold_on, warm_on = _cold_then_warm(compact, exchange_samples,
                                              shards)
    finally:
        tracing.enable(False)
    assert _answer(cold_on) == _answer(cold_off)
    assert _answer(warm_on) == _answer(warm_off)


def test_tracing_off_annotates_and_waits_for_nothing(monkeypatch):
    calls = {"annotation": 0, "wait": 0}
    annotation, wait = jax.profiler.TraceAnnotation, jax.block_until_ready

    def counted_annotation(*a, **kw):
        calls["annotation"] += 1
        return annotation(*a, **kw)

    def counted_wait(x):
        calls["wait"] += 1
        return wait(x)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counted_annotation)
    monkeypatch.setattr(jax, "block_until_ready", counted_wait)
    _cold_then_warm(False, 64)
    assert calls == {"annotation": 0, "wait": 0}
    # the same request with tracing on does both, so the counters can see
    tracing.enable(True)
    try:
        _cold_then_warm(False, 64)
    finally:
        tracing.enable(False)
    assert calls["annotation"] > 0 and calls["wait"] > 0


def _host_events(trace_dir):
    """(name, start, end, stats) of every ``test.``/``hfel.`` host event."""
    from jax.profiler import ProfileData

    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("test.", "hfel.")):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_profiler_trace_holds_every_span_nested(tmp_path, traced):
    sc = SPACES[False]()
    sc2, delta = perturb_scenario(sc, seed=1, **CHURN)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.cold"):
            eng = FastAssociationEngine(sc, kind="fast", seed=0,
                                        profile="coarse", rel_tol=1e-4,
                                        compact=False)
            eng.run("nearest", exchange_samples=64)
        cold_counts = dict(eng.last_counts)
        with jax.profiler.TraceAnnotation("test.warm"):
            eng.rerun_incremental(sc2, delta, exchange_samples=64)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    by_name = {}
    for ev in events:
        by_name.setdefault(ev[0], []).append(ev)
    cold, warm = by_name["test.cold"][0], by_name["test.warm"][0]
    for name in BUILD_SPANS + SWEEP_SPANS + ("hfel.finalize",):
        assert any(_inside(ev, cold) for ev in by_name[name]), name
    for name in SWEEP_SPANS[1:] + ("hfel.rerun.patch", "hfel.rerun.repair",
                                   "hfel.finalize"):
        assert any(_inside(ev, warm) for ev in by_name[name]), name
    # one request's device phases in order, each closed before the next
    phases = [next(ev for ev in by_name[name] if _inside(ev, cold))
              for name in ("hfel.sweep.init", "hfel.sweep.loop",
                           "hfel.readback", "hfel.finalize")]
    assert all(a[2] <= b[1] for a, b in zip(phases, phases[1:]))
    counts = by_name["hfel.count"]
    assert [_inside(ev, cold) for ev in counts] == [True, False]
    assert counts[0][3] == cold_counts
    assert counts[1][3] == {k: eng.last_counts[k] for k in COUNT_NAMES}
    assert set(cold_counts) == set(COUNT_NAMES)
    # the warm re-solve's host counts: one event, inside the warm request
    (rerun,) = by_name["hfel.rerun.count"]
    assert _inside(rerun, warm)
    assert rerun[3] == {k: eng.last_counts[k] for k in RERUN_COUNT_NAMES}
    assert set(eng.last_counts) == set(COUNT_NAMES + RERUN_COUNT_NAMES)
    assert eng.last_counts["departed"] == int(delta.departed.sum())


def test_span_durations_are_taken_per_request():
    """Tracing on, every closed span keeps its host duration until a caller
    takes them; off, ``span`` is the one shared no-op and nothing is kept."""
    tracing.take_durations()
    _cold_then_warm(False, 0)
    assert tracing.take_durations() == {}
    assert tracing.span("hfel.x") is tracing.span("hfel.y")
    tracing.enable(True)
    try:
        eng = _engine(False)
        eng.run("nearest", exchange_samples=0)
        cold = tracing.take_durations()
        sc2, delta = perturb_scenario(eng.sc, seed=1, **CHURN)
        eng.rerun_incremental(sc2, delta, exchange_samples=0)
        warm = tracing.take_durations()
    finally:
        tracing.enable(False)
    assert set(cold) >= set(SWEEP_SPANS + ("hfel.finalize",))
    assert set(warm) >= set(SWEEP_SPANS[1:] + ("hfel.rerun.patch",
                                                "hfel.rerun.repair"))
    assert "hfel.rerun.patch" not in cold
    for spans in (cold, warm):
        assert all(len(v) >= 1 and min(v) > 0.0 for v in spans.values())
    assert len(warm["hfel.sweep.init"]) == 1
    assert tracing.take_durations() == {}


class _Trainer:
    """What a round hook touches of the trainer."""

    client_mask = None

    def readmit_clients(self, *args):
        pass


def test_live_round_spans(tmp_path, traced):
    sc = SPACES[False]()
    runner = LiveHFELRunner(sc, sc.n_devices, churn=CHURN, seed=0,
                            rel_tol=1e-4, exchange_samples=64)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for r in range(2):
            runner.begin_round(_Trainer(), r)
    finally:
        jax.profiler.stop_trace()
    names = [ev[0] for ev in _host_events(tmp_path)]
    # round 0 solves cold; round 1 churns and re-solves warm; both account
    assert names.count("hfel.live.assoc") == 2
    assert names.count("hfel.live.churn") == 1
    assert names.count("hfel.live.accounting") == 2
    assert names.count("hfel.rerun.patch") == 1


def _refresh_widths(eng):
    return [bd.idx.shape[1] + 1 for bd in eng._buckets]


@pytest.mark.parametrize("exchange_samples", [0, 64])
@pytest.mark.parametrize("compact", list(SPACES), ids=SPACE_IDS)
def test_counters_match_the_closed_form(compact, exchange_samples,
                                        monkeypatch):
    eng = _engine(compact)
    eng.run("nearest", exchange_samples=exchange_samples, finalize=False)
    c, moves = eng.last_counts, eng.last_moves
    widths = _refresh_widths(eng)
    rows = [bd.idx.shape[0] for bd in eng._buckets]
    assert c["init_groups"] == sum(r * w for r, w in zip(rows, widths))
    assert c["stale_rows"] == eng.sc.n_servers
    assert c["iterations"] == moves + 1
    assert c["transfers"] + c["exchanges"] == moves
    assert c["exchange_tries"] >= (1 if exchange_samples else 0)
    exchange_groups = 2 * exchange_samples * c["exchange_tries"]
    refresh_groups = c["loop_groups"] - exchange_groups
    if len(widths) == 1:
        assert refresh_groups == 2 * widths[0] * moves
        # one bucket, unsharded: every applied move's two rows in one solve
        assert c["fused_refreshes"] == moves
    else:
        assert 2 * min(widths) * moves <= refresh_groups
        assert refresh_groups <= 2 * max(widths) * moves
        assert c["fused_refreshes"] == 0

    # warm: only the stale rows are re-priced at init
    seen = {}
    init = assoc_fast._init_cache

    def spy(*args, **kw):
        seen["stale"] = np.asarray(args[4])
        return init(*args, **kw)

    monkeypatch.setattr(assoc_fast, "_init_cache", spy)
    sc2, delta = perturb_scenario(eng.sc, seed=1, **CHURN)
    eng.rerun_incremental(sc2, delta, exchange_samples=exchange_samples,
                          finalize=False)
    stale, c = seen["stale"], eng.last_counts
    assert c["stale_rows"] == int(stale.sum())
    assert c["init_groups"] == sum(
        int(stale[np.asarray(bd.servers)].sum()) * w
        for bd, w in zip(eng._buckets, widths))


@pytest.mark.parametrize("shards", [1, 4], ids=["p1", "p4"])
def test_sharded_counters_count_the_padding(shards):
    """Under sharding every bucket's rows pad to a multiple of the mesh and
    the exchange samples to a multiple of it; those are priced, and
    counted, on every shard."""
    if shards > len(jax.devices()):
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count"
                    " (scripts/tier1.sh exports it)")
    single, sharded = _engine("bucketed"), _engine("bucketed", shards)
    single.run("nearest", exchange_samples=7, finalize=False)
    sharded.run("nearest", exchange_samples=7, finalize=False)
    a, b = single.last_counts, sharded.last_counts
    padded = sum(bd.idx.shape[0] * (bd.idx.shape[1] + 1)
                 for bd in sharded._buckets)
    assert b["init_groups"] == padded >= a["init_groups"]
    for name in ("stale_rows", "iterations", "transfers", "exchange_tries",
                 "exchanges"):
        assert b[name] == a[name], name
    assert a["fused_refreshes"] == b["fused_refreshes"] == 0
    per_try = 2 * -(-7 // shards) * shards
    assert (b["loop_groups"] - per_try * b["exchange_tries"]
            == a["loop_groups"] - 14 * a["exchange_tries"])
