"""The comparison that decides ``correct`` has to fail what it guards
against, at a size a CPU test run holds:

* the control (the plain reference in bfloat16, put in the program's place)
  fails at least one of the cell's numbers where the program's own answers
  pass every one;
* a run with the timed path broken underneath reports ``correct`` false,
  once for each fault the cell can have: a step that returns its state
  unchanged, half of the devices left out of the descent, and an answer
  altered where it is produced. (The cells run on one chip, so no exchange
  between chips can be left out.)
"""

import dataclasses
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from benchlib import harness, spec  # noqa: E402
from benchlib.harness import judge  # noqa: E402

import control  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
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


def verdict(cell, capsys, seed=2**31 + 99, seconds=2.0):
    harness.main(["--workload", cell.name, "--seed", str(seed),
                  "--seconds", str(seconds), "--trace", "0"],
                 cell=cell, device=CPU)
    out, _ = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_where_the_program_passes(name):
    cell = tiny(name)
    (line,) = control.control_runs(cell, [2**31 + 7], 2.0)
    limits = cell.limits["limits"]
    assert all(c["ok"] for c in judge(line["program"], limits).values())
    assert not all(c["ok"] for c in judge(line["control"], limits).values())


# ---- faults planted under the timed path --------------------------------

def _nearest(sc):
    dist = np.where(np.asarray(sc.avail), np.asarray(sc.dist), np.inf)
    return np.argmin(dist, axis=0)


def _other_server(sc, assign, devices):
    """Each device moved to a server it cannot reach where there is one,
    else to the next server."""
    out = assign.copy()
    reach = np.asarray(sc.avail)
    for n in devices:
        far = np.flatnonzero(~reach[:, n])
        out[n] = far[0] if far.size else (assign[n] + 1) % sc.n_servers
    return out


def solve_unchanged(orig):
    def run(self, *a, **kw):
        return orig(self, *a, **dict(kw, max_moves=0))
    return run


def solve_half(orig):
    def run(self, *a, **kw):
        res = orig(self, *a, **kw)
        half = np.arange(self.sc.n_devices)[::2]
        res.assignment[half] = _nearest(self.sc)[half]
        return res
    return run


def solve_altered(orig):
    def run(self, *a, **kw):
        res = orig(self, *a, **kw)
        res.assignment[:] = _other_server(self.sc, res.assignment, [0])
        return res
    return run


# each fault of a cell whose traffic kind is ``cold_solve``
SOLVE_FAULTS = (solve_unchanged, solve_half, solve_altered)


@pytest.mark.parametrize("name,fault", [
    (name, fault) for name in CELLS
    if spec.load_cell(name, SPEC).traffic["loop"] == "cold_solve"
    for fault in SOLVE_FAULTS])
def test_a_broken_timed_path_is_not_correct(name, fault, capsys,
                                            monkeypatch):
    from repro.core.assoc_fast import FastAssociationEngine

    cell = tiny(name)
    orig = FastAssociationEngine.run
    monkeypatch.setattr(FastAssociationEngine, "run", fault(orig))
    res = verdict(cell, capsys)
    assert res["correct"] is False, res["checks"]
