"""Capacity-constrained live HFEL: streaming admission under per-edge caps.

Every edge server carries a hard ``max_devices`` cap (``cap_slack`` sizes
caps off the nearest-server load profile). The live loop then splits the
population three ways each round:

  * admitted  — in the association view, training, counted against caps;
  * queued    — arrived (or displaced) devices no edge can admit yet; they
    wait in a bounded FIFO overflow queue, OUT of training;
  * rejected  — dropped off the queue's tail when it overflows
    ``overflow_max`` (they re-enter only by departing and re-arriving).

Admission is the O(K)-per-device ``greedy_admission`` path — a
nearest-with-headroom placement that never wakes the solver; the periodic
global re-solves (``resolve_every``) rebalance load and free headroom,
which the post-resolve admission tick immediately drains.

    PYTHONPATH=src python examples/streaming_admission.py
"""

import numpy as np

from repro.core import make_large_scenario
from repro.data import make_mnist_like
from repro.fl import run_live
from repro.utils.compile_cache import enable_compile_cache

enable_compile_cache()

N, K = 32, 4

# cap_slack=1.0 sizes each cap EXACTLY at the nearest-server count: zero
# global slack, so churn reliably pushes arrivals into the overflow queue
sc = make_large_scenario(N, K, seed=0, cap_slack=1.0)
print(f"per-edge caps {sc.capacity} (sum {sc.capacity.sum()}, N={N})")

ds = make_mnist_like(N, samples_total=800, seed=0)
churn = dict(drift_m=60.0, move_frac=0.2, flip_frac=0.1,
             depart_frac=0.2, arrive_frac=0.5)
h = run_live(sc, ds, policy="incremental-warm", rounds=8, resolve_every=2,
             churn=churn, seed=0, local_iters=2, edge_iters=2,
             overflow_max=16, verify=True)

print("\nround  active  queued  admitted  rejected  resolve  cost")
for r in range(h.rounds):
    print(f"{r:>5}  {h.n_active[r]:>6}  {h.n_queued[r]:>6}  "
          f"{h.n_admitted[r]:>8}  {h.n_rejected[r]:>8}  "
          f"{'yes' if h.swapped[r] else '':>7}  {h.system_cost[r]:>8.1f}")

print(f"\n{sum(h.n_admitted)} devices streamed in through the admission "
      f"path; {sum(h.n_rejected)} dropped from the overflow queue")
print(f"final test acc {h.train.test_acc[-1]:.3f} — training stayed sound "
      "while the admitted population floated under the caps")
assert sum(h.n_admitted) > 0 and h.rounds == 8
