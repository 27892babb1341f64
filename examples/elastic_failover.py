"""Fault-tolerance demo: hierarchical FL training under node failures with
elastic edge re-association (Alg. 3 warm-started) and straggler dropping.

    PYTHONPATH=src python examples/elastic_failover.py
"""

import jax.numpy as jnp
import numpy as np

from repro.core import make_scenario
from repro.data import make_mnist_like
from repro.fl import train_federated
from repro.runtime import ElasticReassociator, FailureInjector
from repro.utils.compile_cache import enable_compile_cache

enable_compile_cache()

N, K = 20, 4

sc = make_scenario(N, K, seed=0)
er = ElasticReassociator(sc, seed=0)
initial = er.initial()
print(f"initial association cost {initial.total_cost:.1f} "
      f"({initial.n_adjustments} adjustments)")

ds = make_mnist_like(N, seed=0)
fi = FailureInjector(N, p_fail=0.08, p_recover=0.4, seed=3)
assignment_box = {"a": jnp.asarray(initial.assignment)}
events = []


def hook(trainer, r):
    alive = fi.step()
    trainer.client_mask = jnp.asarray(alive)
    if alive.sum() < N:   # membership changed -> re-associate live devices
        res = er.on_membership_change(alive)
        assignment_box["a"] = jnp.asarray(res.assignment)
        events.append((r, int(alive.sum()), res.n_adjustments,
                       round(res.total_cost, 1)))


hist = train_federated(ds, method="hfel",
                       assignment=np.asarray(initial.assignment),
                       n_servers=K, rounds=15, local_iters=10, edge_iters=5,
                       lr=0.05, eval_every=3, round_hook=hook)

print("\nfailure/re-association events (round, alive, adjustments, cost):")
for e in events[:10]:
    print(" ", e)
print(f"\nfinal test acc {hist.test_acc[-1]:.3f} "
      f"(training stayed sound through {len(events)} failure rounds)")
