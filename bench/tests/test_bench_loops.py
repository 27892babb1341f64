"""CPU tests of each cell's request loop at a tiny size, steered from the
test (the chip check is skipped by handing the harness a device record),
and of what the benchmark refuses to do off the chip."""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from benchlib import harness, spec  # noqa: E402
from benchlib.scenarios import make_deployment  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
KEYS = ["correct", "attempted", "failed", "metrics", "device"]
SPEC = spec.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
# a size at which the optimal kind's nested solver runs a request in
# seconds on a CPU; one warm-up request compiles every program
TINY = {"n_devices": 12, "n_servers": 3}


def tiny(name: str):
    cell = spec.load_cell(name, SPEC)
    return dataclasses.replace(
        cell, config=dict(cell.config, **TINY),
        traffic=dict(cell.traffic, pool=3, warmup_requests=1, warm_moves=40))


def run_cell(cell, capsys, seconds=2.0, seed=2**31 + 12345):
    rc = harness.main(["--workload", cell.name, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", "0"],
                      cell=cell, device=CPU)
    out, err = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("name", CELLS)
def test_cell_loop_prints_the_contract_line(name, capsys):
    cell = tiny(name)
    res, err = run_cell(cell, capsys)
    assert list(res)[:5] == KEYS and list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in res["metrics"].values():
        assert m["value"] > 0
    assert set(res["checks"]) == set(cell.limits["limits"])
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert all(" check " in line and " limit " in line for line in tail)
    assert "compiles in the window: 0 " in err


def test_generator_matches_the_programs_generator():
    from repro.core.scenario import make_scenario

    for name in SPEC["configs"]:
        cfg = json.loads((ROOT / name["file"]).read_text())
        ours = make_deployment(cfg, 5)
        ref = make_scenario(cfg["n_devices"], cfg["n_servers"], seed=5)
        for f in ("avail", "dist", "dev_xy", "srv_xy"):
            np.testing.assert_array_equal(getattr(ours, f), getattr(ref, f))
        for f in dataclasses.fields(ref.dev):
            np.testing.assert_array_equal(getattr(ours.dev, f.name),
                                          getattr(ref.dev, f.name))
        np.testing.assert_array_equal(ours.srv.cloud_rate,
                                      ref.srv.cloud_rate)


def test_every_name_in_the_spec_has_its_file():
    """A cell is its entries and the files they name, found by name."""
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in SPEC["workloads"]:
        cell = spec.load_cell(w["name"], SPEC)
        assert spec.loop_class(cell.traffic["loop"]) is not None
        assert set(cell.limits["limits"]) >= {"unreachable"}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_an_unknown_loop_kind_is_refused():
    with pytest.raises(SystemExit, match="no request loop 'nosuch'"):
        spec.loop_class("nosuch")


def test_end_to_end_readers_read_the_window():
    recs = [{"t": float(t)} for t in range(1, 11)]
    ctx = {"records": recs, "setup_s": 12.5}
    assert spec.metric_reader("setup_s")(ctx) == 12.5
    assert spec.metric_reader("assoc_p50_s")(ctx) == 5.5
    assert spec.metric_reader("assoc_p90_s")(ctx) == pytest.approx(9.9)


def test_run_refuses_a_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip()
