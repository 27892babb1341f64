"""What every request loop shares: seeds derived from the run's seed, the
engine's keyword arguments from a configuration, and the seeded sample of
answers that the check after the window reads."""

from __future__ import annotations

import numpy as np

_SEED_BOUND = 2**31 - 1


def sub_seed(seed: int, *path: int) -> int:
    """A seed below 2**31 for one use, derived from the run's seed."""
    ss = np.random.SeedSequence([int(seed) % (2**63), *path])
    return int(ss.generate_state(1, np.uint64)[0] % _SEED_BOUND)


def engine_kwargs(cfg: dict) -> dict:
    e = cfg["engine"]
    return dict(kind=e["kind"], profile=e["profile"], rel_tol=e["rel_tol"],
                ra_backend=e["ra_backend"], compact=e["compact"],
                permission=e["permission"],
                min_residual_group=e["min_residual_group"])


class Sample:
    """A seeded reservoir of ``size`` answers, plus the slowest one."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self.rng = np.random.default_rng(seed)
        self.kept: dict[int, tuple] = {}
        self.slowest: tuple | None = None
        self.seen = 0

    def offer(self, i: int, item: tuple, t: float) -> None:
        if self.slowest is None or t > self.slowest[0]:
            self.slowest = (t, i, item)
        self.seen += 1
        if len(self.kept) < self.size:
            self.kept[i] = item
            return
        j = int(self.rng.integers(0, self.seen))
        if j < self.size:
            del self.kept[sorted(self.kept)[j]]
            self.kept[i] = item

    def items(self) -> list[tuple]:
        out = dict(self.kept)
        if self.slowest is not None:
            out[self.slowest[1]] = self.slowest[2]
        return [out[i] for i in sorted(out)]


def warm_trace_reads(upto: int, max_moves: int = 10_000) -> None:
    """The engine reads its move trace back as ``trace[:moves + 1]``, one
    small program per distinct move count; compile those for up to ``upto``
    moves in set-up, so that none compiles in the window."""
    import jax.numpy as jnp

    trace = jnp.zeros(max_moves + 1, jnp.float32)
    for m in range(upto):
        np.asarray(trace[:m + 1])
