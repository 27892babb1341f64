"""Quickstart: the paper's core loop in ~40 lines.

Builds a random HFEL scenario (Table II parameters), solves optimal
resource allocation per edge server (Section III), runs edge association to
a stable system point (Section IV), and prints the cost against the
benchmark schemes of §V.A.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import make_scenario
from repro.core.edge_association import AssociationEngine, evaluate_scheme
from repro.utils.compile_cache import enable_compile_cache

enable_compile_cache()

N_DEVICES, N_SERVERS = 20, 5

sc = make_scenario(N_DEVICES, N_SERVERS, seed=0)
print(f"scenario: {N_DEVICES} devices, {N_SERVERS} edge servers, "
      f"L(theta)={sc.lp.local_iters:.1f} local iters, "
      f"I(eps,theta)={sc.lp.edge_iters:.1f} edge iters")

engine = AssociationEngine(sc, kind="fast", seed=0)
res = engine.run_batched("random")
print(f"\nHFEL schedule: cost {res.cost_trace[0]:.1f} -> {res.total_cost:.1f} "
      f"after {res.n_adjustments} permitted adjustments (stable point)")
print("  assignment:", res.assignment.tolist())
print("  per-device CPU GHz:", np.round(res.f / 1e9, 2).tolist())
print("  per-device bandwidth share:", np.round(res.beta, 3).tolist())
print(f"  true eq.(17) cost: {res.true_cost:.1f} "
      f"(E={res.true_energy:.1f} J, T={res.true_delay:.1f} s)")

print("\nbenchmark schemes (global cost, lower is better):")
for scheme in ["hfel", "comp_opt", "greedy", "random", "comm_opt",
               "uniform", "proportional"]:
    r = evaluate_scheme(sc, scheme, seed=0)
    print(f"  {scheme:13s} {r.total_cost:12.1f}")
