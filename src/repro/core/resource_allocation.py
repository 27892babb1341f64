"""Optimal computation/communication resource allocation — paper Section III.

Solves problem (18) for one edge server's training group S_i:

    min  C_i(f, beta) = sum_n [ a_n/beta_n + b_n f_n^2 ]
                        + w * max_n [ d_n/beta_n + e_n/f_n ]
    s.t. sum_n beta_n <= 1,  0 < beta_n <= 1,  f_min <= f_n <= f_max

with the Section-III constants (a, b, d, e, w) from
:func:`repro.core.cost_model.ra_constants`.

Four solvers are provided; all are jit-able and vmap-able over padded groups
(``mask`` selects the active members):

* :func:`solve_paper`        — Algorithm 2 *faithful*: substitute the KKT
  bandwidth rule beta(f) of Theorem 2 / eq. (19), then solve the reduced
  f-only convex problem (32) by a projected first-order method with an
  annealed log-sum-exp smoothing of the max (standing in for the paper's
  "CVX / IPOPT").
* :func:`solve_fixed_point`  — the fast kind: the optimum of (18) from its
  KKT conditions in every regime (:func:`kkt_rows`): golden section over
  the common deadline t, Newton on the bandwidth price nu, and per device
  either the untight f_min case or a Newton root of the tight pair. About
  a thousand dependent steps; prices the many candidate groups of edge
  association.
* :func:`solve_exact`        — exact nested parametric solver: golden-section
  over the deadline t, bisection over the bandwidth multiplier nu, per-device
  golden-section for the (convex) boundary trade-off. Handles all box/cap
  clipping cases; the reported final costs use this.
* :func:`solve_reference`    — plain projected subgradient on (f, beta)
  jointly. Slow, structure-free; the test oracle.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import dataclasses
import jax
import jax.numpy as jnp
from jax import lax

from repro.core.cost_model import RAConstants, ra_objective
from repro.kernels.kkt import EPS as _EPS
from repro.kernels.kkt import golden_min as _golden_min
from repro.kernels.kkt import kkt_rows


def _register(cls):
    fields = [f.name for f in dataclasses.fields(cls)]
    jax.tree_util.register_dataclass(cls, data_fields=fields, meta_fields=[])
    return cls


@_register
@dataclass
class RASolution:
    f: jnp.ndarray          # (N,) optimal CPU frequencies (padded: f_min)
    beta: jnp.ndarray       # (N,) optimal bandwidth shares (padded: 0)
    cost: jnp.ndarray       # scalar, optimal value of (18); 0 for empty group
    deadline: jnp.ndarray   # scalar t* = max_n d/beta + e/f


def _masked_beta_norm(s: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Normalize positive scores s to sum to 1 over the active set."""
    s = jnp.where(mask, s, 0.0)
    tot = jnp.maximum(jnp.sum(s), _EPS)
    return jnp.where(mask, s / tot, 0.0)


def _finalize(c: RAConstants, mask, f, beta) -> RASolution:
    any_active = jnp.any(mask)
    f = jnp.where(mask, jnp.clip(f, c.f_min, c.f_max), c.f_min)
    beta = _masked_beta_norm(jnp.maximum(beta, _EPS), mask)
    safe_beta = jnp.where(mask, jnp.maximum(beta, _EPS), 1.0)
    cost = jnp.where(any_active, ra_objective(c, mask, f, safe_beta), 0.0)
    deadline = jnp.max(jnp.where(mask, c.d / safe_beta + c.e / f, 0.0))
    return RASolution(f=f, beta=beta, cost=cost, deadline=deadline)


# ---------------------------------------------------------------------------
# Theorem 2: the closed-form bandwidth rule, eq. (19)
# ---------------------------------------------------------------------------

def beta_of_f(c: RAConstants, mask: jnp.ndarray, f: jnp.ndarray) -> jnp.ndarray:
    """beta*_n  proportional to  (a_n + (2 b_n f_n^3 / e_n) d_n)^(1/3)."""
    tau = 2.0 * c.b * f**3 / jnp.maximum(c.e, _EPS)
    score = jnp.cbrt(jnp.maximum(c.a + tau * c.d, _EPS))
    return _masked_beta_norm(score, mask)


# ---------------------------------------------------------------------------
# Solver 1 — Algorithm 2 (paper-faithful)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n_steps",))
def solve_paper(c: RAConstants, mask: jnp.ndarray, *, n_steps: int = 400) -> RASolution:
    """Algorithm 2: replace beta by eq. (19), solve (32) over f only.

    The max term of (32) is smoothed with an annealed log-sum-exp
    (temperature decays geometrically), and the box constraint on f is kept
    by projection. Adam is used as the first-order engine — the role the
    paper assigns to an off-the-shelf convex solver.
    """
    n = c.a.shape[0]

    def objective(f, temp):
        beta = beta_of_f(c, mask, f)
        safe_beta = jnp.where(mask, jnp.maximum(beta, _EPS), 1.0)
        s = jnp.sum(jnp.where(mask, c.a / safe_beta + c.b * f**2, 0.0))
        per_max = jnp.where(mask, c.d / safe_beta + c.e / f, -jnp.inf)
        # temperature-scaled LSE -> max as temp -> 0
        m = temp * jax.nn.logsumexp(per_max / temp)
        return s + c.w * m

    grad_fn = jax.grad(objective)
    f0 = jnp.sqrt(c.f_min * c.f_max)
    scale = c.f_max - c.f_min
    t_hot = jnp.asarray(1e2, jnp.float32)
    decay = (1e-4 / 1e2) ** (1.0 / max(n_steps - 1, 1))

    def step(carry, _):
        f, m1, m2, k, temp = carry
        g = grad_fn(f, temp) * scale          # precondition by box width
        m1 = 0.9 * m1 + 0.1 * g
        m2 = 0.999 * m2 + 0.001 * g * g
        m1h = m1 / (1 - 0.9 ** (k + 1))
        m2h = m2 / (1 - 0.999 ** (k + 1))
        f = f - 0.02 * scale * m1h / (jnp.sqrt(m2h) + 1e-8)
        f = jnp.clip(f, c.f_min, c.f_max)
        return (f, m1, m2, k + 1, temp * decay), None

    init = (f0, jnp.zeros(n), jnp.zeros(n), jnp.asarray(0), t_hot)
    (f, _, _, _, _), _ = lax.scan(step, init, None, length=n_steps)
    return _finalize(c, mask, f, beta_of_f(c, mask, f))


# ---------------------------------------------------------------------------
# Solver 2 — KKT solve (the fast kind)
# ---------------------------------------------------------------------------

def _deadline_bracket(c: RAConstants, mask, n_bracket: int = 60):
    """Feasible deadline range.

    Lower: smallest t with sum_n d_n/(t - e_n/f_max) <= 1 (every device at
    max frequency, bandwidth exactly exhausted). Upper: same with f_min.
    Both bisections run simultaneously on a stacked (2, N) array so the
    sequential depth is ``n_bracket`` steps, not 2x that.
    """
    f2 = jnp.stack([c.f_max, c.f_min])                         # (2, N)

    def sum_beta_min(t):
        slack = t[:, None] - c.e / f2
        b = jnp.where(mask, c.d / jnp.maximum(slack, _EPS), 0.0)
        b = jnp.where(mask & (slack <= 0), 1e6, b)
        return jnp.sum(b, axis=-1)

    lo = jnp.max(jnp.where(mask, c.e / f2 + c.d, 0.0), axis=-1)  # device floor
    hi = lo + jnp.sum(jnp.where(mask, c.d, 0.0)) * 1e4 + 1.0

    def body(_, lohi):
        lo_, hi_ = lohi
        mid = 0.5 * (lo_ + hi_)
        ok = sum_beta_min(mid) <= 1.0
        return (jnp.where(ok, lo_, mid), jnp.where(ok, mid, hi_))

    lo_, hi_ = lax.fori_loop(0, n_bracket, body, (lo, hi))
    return hi_[0], hi_[1]


# Iteration presets for :func:`solve_fixed_point` (see :func:`kkt_rows`):
# golden-section steps over the deadline, Newton steps of the bandwidth
# price, Newton steps of each device's communication time at each price,
# and bisection steps of the least feasible deadline. "default" prices
# groups within ~1e-6 of the optimum of (18) on a CPU; "screen" / "coarse"
# trade that for a shallower solve (dependent depth 1158, 714 and 491
# steps) when the solver runs inside the fused candidate sweeps of
# ``repro.core.assoc_fast``.
SCREEN_PROFILES: dict[str, dict[str, int]] = {
    "default": dict(n_golden=24, n_price=5, n_root=5, n_bracket=24),
    "screen": dict(n_golden=20, n_price=4, n_root=4, n_bracket=24),
    "coarse": dict(n_golden=16, n_price=4, n_root=3, n_bracket=16),
}

# Named multi-tier descent plans for the association engines: each entry is a
# sequence of SCREEN_PROFILES names run back-to-back, every tier warm-started
# from the previous tier's stable assignment. The cheap leading tiers apply
# the bulk of the adjustments; the trailing "default" tier polishes the
# stable point back to reference accuracy at a few moves' cost.
TIER_PLANS: dict[str, tuple[str, ...]] = {
    "default_only": ("default",),
    "two_tier": ("coarse", "default"),
    "three_tier": ("coarse", "screen", "default"),
}


def resolve_tiers(tiers) -> tuple[str, ...]:
    """Normalize a tier spec into a tuple of screening-profile names.

    Accepts a :data:`TIER_PLANS` plan name, a single profile name, or an
    iterable of profile names; every resolved profile must exist in
    :data:`SCREEN_PROFILES`.
    """
    if isinstance(tiers, str):
        tiers = TIER_PLANS.get(tiers, (tiers,))
    tiers = tuple(tiers)
    if not tiers:
        raise ValueError("tier plan resolves to no profiles")
    unknown = [t for t in tiers if t not in SCREEN_PROFILES]
    if unknown:
        raise ValueError(
            f"unknown screening profile(s) {unknown}; expected names from "
            f"SCREEN_PROFILES {sorted(SCREEN_PROFILES)} or a TIER_PLANS "
            f"plan {sorted(TIER_PLANS)}")
    return tiers


@partial(jax.jit, static_argnames=("n_golden", "n_price", "n_root",
                                   "n_bracket"))
def solve_fixed_point(c: RAConstants, mask: jnp.ndarray, *, n_golden: int = 24,
                      n_price: int = 5, n_root: int = 5,
                      n_bracket: int = 24) -> RASolution:
    """The fast kind's group solve: the optimum of (18) from its KKT
    conditions (:func:`kkt_rows`), at a dependent depth of about a
    thousand steps where :func:`solve_exact` takes ~81k.

    ``(n_golden, n_price, n_root, n_bracket)`` presets live in
    :data:`SCREEN_PROFILES`.
    """
    f, beta, cost, deadline = kkt_rows(
        c.a, c.b, c.d, c.e, c.w, c.f_min, c.f_max, mask, n_golden=n_golden,
        n_price=n_price, n_root=n_root, n_bracket=n_bracket)
    return RASolution(f=f, beta=beta, cost=cost[0], deadline=deadline[0])


def solve_fixed_point_batched(c: RAConstants, masks: jnp.ndarray, *,
                              n_golden: int = 24, n_price: int = 5,
                              n_root: int = 5, n_bracket: int = 24,
                              backend: str = "xla") -> RASolution:
    """Solve a BATCH of independent groups with the fast kind's KKT solve.

    ``c`` holds the constants batched over groups — leaves ``(G, R)``, ``w``
    ``(G,)`` — and ``masks`` is ``(G, R)``. ``backend`` selects the engine:

    * ``"xla"`` — :func:`solve_fixed_point` vmapped over the batch; the
      traced per-group graph is identical to the scalar solver's, so results
      are bit-identical to solving each group alone.
    * ``"pallas"`` — the fused :mod:`repro.kernels.golden_section` kernel
      (interpret mode off-TPU): the same :func:`kkt_rows` runs as one
      VMEM-resident kernel per group block. It matches the XLA path to
      float32 rounding, not bit-exactly; parity is pinned at rtol 2e-4 on
      cost (tests/test_assoc_sharded.py).
    """
    iters = dict(n_golden=n_golden, n_price=n_price, n_root=n_root,
                 n_bracket=n_bracket)
    if backend == "xla":
        return jax.vmap(lambda cc, m: solve_fixed_point(cc, m, **iters))(
            c, masks)
    if backend == "pallas":
        from repro.kernels import ops as _kops
        f, beta, cost, deadline = _kops.golden_section_solve(
            c.a, c.b, c.d, c.e, c.w, c.f_min, c.f_max, masks, **iters)
        return RASolution(f=f, beta=beta, cost=cost, deadline=deadline)
    raise ValueError(f"unknown RA backend {backend!r}; "
                     "expected 'xla' or 'pallas'")


# ---------------------------------------------------------------------------
# Solver 3 — exact nested parametric solver (beyond-paper)
# ---------------------------------------------------------------------------

def _inner_beta_f(c: RAConstants, mask, t, nu, n_beta: int = 32):
    """For fixed (deadline t, bandwidth price nu): per-device minimize

        psi(beta) = a/beta + b * f(beta)^2 + nu*beta,
        f(beta)   = clip(e / (t - d/beta), f_min, f_max)

    over beta in [beta_feas(t), 1]. psi is convex (see DESIGN.md §2);
    vectorized golden-section across devices.
    """
    # feasible lower end: meet deadline at f_max
    slack_max = t - c.e / c.f_max
    b_lo = jnp.where(slack_max > 0, c.d / jnp.maximum(slack_max, _EPS), 1.0)
    b_lo = jnp.clip(b_lo, _EPS, 1.0)
    b_hi = jnp.ones_like(b_lo)

    def f_of_beta(beta):
        slack = t - c.d / jnp.maximum(beta, _EPS)
        f = jnp.where(slack > 0, c.e / jnp.maximum(slack, _EPS), c.f_max)
        return jnp.clip(f, c.f_min, c.f_max)

    def psi(beta):
        f = f_of_beta(beta)
        return c.a / jnp.maximum(beta, _EPS) + c.b * f**2 + nu * beta

    beta = _golden_min(psi, b_lo, b_hi, n_beta)    # vectorized across devices
    return beta, f_of_beta(beta)


def _solve_fixed_t(c: RAConstants, mask, t, n_nu: int = 40):
    """Exact inner solve at fixed deadline t: bisect the bandwidth price nu
    so that the active betas sum to 1 (sum beta decreasing in nu)."""
    def sum_beta(nu):
        beta, _ = _inner_beta_f(c, mask, t, nu)
        return jnp.sum(jnp.where(mask, beta, 0.0))

    # bracket: nu=0 gives each beta -> its unconstrained max (sum >= 1 when
    # the simplex binds); grow hi until sum <= 1.
    def grow(_, hi):
        return jnp.where(sum_beta(hi) > 1.0, hi * 8.0, hi)

    hi = lax.fori_loop(0, 12, grow, jnp.asarray(1.0, jnp.float32))
    simplex_binds = sum_beta(jnp.asarray(0.0, jnp.float32)) > 1.0

    def body(_, lohi):
        lo_, hi_ = lohi
        mid = 0.5 * (lo_ + hi_)
        over = sum_beta(mid) > 1.0
        return (jnp.where(over, mid, lo_), jnp.where(over, hi_, mid))

    lo_, hi_ = lax.fori_loop(0, n_nu, body, (jnp.asarray(0.0, jnp.float32), hi))
    nu = jnp.where(simplex_binds, 0.5 * (lo_ + hi_), 0.0)
    beta, f = _inner_beta_f(c, mask, t, nu)
    value = jnp.sum(jnp.where(mask, c.a / jnp.maximum(beta, _EPS) + c.b * f**2, 0.0))
    return beta, f, value


@partial(jax.jit, static_argnames=("n_outer",))
def solve_exact(c: RAConstants, mask: jnp.ndarray, *, n_outer: int = 44) -> RASolution:
    """Golden-section over t of J(t) = inner_value(t) + w*t (convex)."""
    t_lo, t_hi = _deadline_bracket(c, mask)
    t_lo = t_lo * (1.0 + 1e-6)
    t_hi = jnp.maximum(t_hi * 2.0, t_lo * 4.0)

    def j_of_t(t):
        _, _, value = _solve_fixed_t(c, mask, t)
        return value + c.w * t

    t_star = _golden_min(j_of_t, t_lo, t_hi, n_outer)
    beta, f, _ = _solve_fixed_t(c, mask, t_star)
    return _finalize(c, mask, f, beta)


# ---------------------------------------------------------------------------
# Solver 4 — projected subgradient reference (test oracle)
# ---------------------------------------------------------------------------

def _project_simplex_cap(beta: jnp.ndarray, mask: jnp.ndarray,
                         lo: float = 1e-6) -> jnp.ndarray:
    """Euclidean projection onto {lo <= beta_n <= 1, sum_active beta <= 1}."""
    n_active = jnp.maximum(jnp.sum(mask), 1)
    beta = jnp.clip(jnp.where(mask, beta, 0.0), lo, 1.0)
    need = jnp.sum(beta) > 1.0

    # bisection on the shift s: sum clip(beta - s, lo, 1) = 1
    def body(_, lohi):
        l, h = lohi
        mid = 0.5 * (l + h)
        tot = jnp.sum(jnp.where(mask, jnp.clip(beta - mid, lo, 1.0), 0.0))
        return (jnp.where(tot > 1.0, mid, l), jnp.where(tot > 1.0, h, mid))

    l, h = lax.fori_loop(0, 50, body, (jnp.asarray(0.0), jnp.max(beta)))
    shifted = jnp.clip(beta - 0.5 * (l + h), lo, 1.0)
    out = jnp.where(need, shifted, beta)
    return jnp.where(mask, out, 0.0)


@partial(jax.jit, static_argnames=("n_steps",))
def solve_reference(c: RAConstants, mask: jnp.ndarray, *, n_steps: int = 4000,
                    seed: int = 0) -> RASolution:
    """Projected subgradient on (f, beta) jointly; keeps the best iterate."""
    def objective(fb):
        f, beta = fb
        safe_beta = jnp.where(mask, jnp.maximum(beta, _EPS), 1.0)
        return ra_objective(c, mask, f, safe_beta)

    grad_fn = jax.grad(objective)
    f0 = jnp.sqrt(c.f_min * c.f_max)
    b0 = _project_simplex_cap(jnp.where(mask, 1.0, 0.0) /
                              jnp.maximum(jnp.sum(mask), 1), mask)

    def step(carry, k):
        f, beta, best_f, best_b, best_v = carry
        gf, gb = grad_fn((f, beta))
        lr = 1.0 / jnp.sqrt(k + 1.0)
        f = jnp.clip(f - lr * (c.f_max - c.f_min) * 0.1 *
                     gf / (jnp.abs(gf) + 1e-20), c.f_min, c.f_max)
        beta = _project_simplex_cap(
            beta - lr * 0.05 * gb / (jnp.linalg.norm(gb) + 1e-20), mask)
        v = objective((f, beta))
        better = v < best_v
        best = (jnp.where(better, f, best_f), jnp.where(better, beta, best_b),
                jnp.where(better, v, best_v))
        return (f, beta, *best), None

    init = (f0, b0, f0, b0, objective((f0, b0)))
    (_, _, best_f, best_b, _), _ = lax.scan(step, init, jnp.arange(n_steps))
    return _finalize(c, mask, best_f, best_b)


# ---------------------------------------------------------------------------
# Partial-optimization variants for the paper's §V.A benchmark schemes
# ---------------------------------------------------------------------------

@jax.jit
def optimize_f_given_beta(c: RAConstants, mask: jnp.ndarray,
                          beta: jnp.ndarray) -> RASolution:
    """"Computation optimization" scheme: optimal f under a fixed beta.

    Exact via golden-section on the deadline: at fixed t the objective is
    increasing in f so f_n(t) = clip(e_n/(t - d_n/beta_n), box); the value
    U(t) = sum b f(t)^2 + w t is convex in t.
    """
    safe_beta = jnp.where(mask, jnp.maximum(beta, _EPS), 1.0)
    floor = c.d / safe_beta
    t_lo = jnp.max(jnp.where(mask, floor + c.e / c.f_max, 0.0)) * (1 + 1e-6)
    t_hi = jnp.max(jnp.where(mask, floor + c.e / c.f_min, 0.0)) * 1.5 + 1.0

    def f_of_t(t):
        slack = t - floor
        f = jnp.where(slack > 0, c.e / jnp.maximum(slack, _EPS), c.f_max)
        return jnp.clip(f, c.f_min, c.f_max)

    def u_of_t(t):
        f = f_of_t(t)
        return jnp.sum(jnp.where(mask, c.b * f**2, 0.0)) + c.w * t

    f = f_of_t(_golden_min(u_of_t, t_lo, t_hi, 48))
    any_active = jnp.any(mask)
    cost = jnp.where(any_active, ra_objective(c, mask, f, safe_beta), 0.0)
    deadline = jnp.max(jnp.where(mask, c.d / safe_beta + c.e / f, 0.0))
    return RASolution(f=jnp.where(mask, f, c.f_min),
                      beta=jnp.where(mask, beta, 0.0), cost=cost,
                      deadline=deadline)


@jax.jit
def optimize_beta_given_f(c: RAConstants, mask: jnp.ndarray,
                          f: jnp.ndarray) -> RASolution:
    """"Communication optimization" scheme: optimal beta under a fixed f.

    Exact: golden-section over t with an inner water-filling
    beta_n(t, nu) = max(d_n/(t - e_n/f_n), sqrt(a_n/nu)) and bisection on nu
    for sum beta = 1.
    """
    e_over_f = c.e / jnp.clip(f, c.f_min, c.f_max)

    def betas(t, nu):
        b_floor = jnp.where(t > e_over_f,
                            c.d / jnp.maximum(t - e_over_f, _EPS), 1.0)
        b_free = jnp.sqrt(c.a / jnp.maximum(nu, _EPS))
        return jnp.clip(jnp.maximum(b_floor, b_free), _EPS, 1.0)

    def solve_nu(t):
        def sum_b(nu):
            return jnp.sum(jnp.where(mask, betas(t, nu), 0.0))

        hi0 = jnp.asarray(1.0, jnp.float32)
        hi = lax.fori_loop(0, 14, lambda _, h: jnp.where(sum_b(h) > 1, h * 8, h), hi0)

        def body(_, lohi):
            l, h = lohi
            mid = 0.5 * (l + h)
            return (jnp.where(sum_b(mid) > 1, mid, l),
                    jnp.where(sum_b(mid) > 1, h, mid))

        l, h = lax.fori_loop(0, 44, body, (jnp.asarray(0.0, jnp.float32), hi))
        return 0.5 * (l + h)

    # feasible t: sum of beta floors <= 1
    def sum_floor(t):
        b = jnp.where(t > e_over_f, c.d / jnp.maximum(t - e_over_f, _EPS), 1e6)
        return jnp.sum(jnp.where(mask, b, 0.0))

    lo0 = jnp.max(jnp.where(mask, e_over_f + c.d, 0.0))
    hi0 = lo0 + jnp.sum(jnp.where(mask, c.d, 0.0)) * 1e4 + 1.0

    def fbody(_, lohi):
        l, h = lohi
        mid = 0.5 * (l + h)
        ok = sum_floor(mid) <= 1.0
        return (jnp.where(ok, l, mid), jnp.where(ok, mid, h))

    _, t_lo = lax.fori_loop(0, 60, fbody, (lo0, hi0))
    t_hi = t_lo * 4.0 + 1.0

    def v_of_t(t):
        beta = betas(t, solve_nu(t))
        return jnp.sum(jnp.where(mask, c.a / beta, 0.0)) + c.w * t

    t_star = _golden_min(v_of_t, t_lo * (1 + 1e-6), t_hi, 44)
    beta = _masked_beta_norm(betas(t_star, solve_nu(t_star)), mask)
    return _finalize(c, mask, jnp.clip(f, c.f_min, c.f_max), beta)


# ---------------------------------------------------------------------------
# Dispatcher
# ---------------------------------------------------------------------------

SOLVERS = {
    "paper": solve_paper,
    "fixed_point": solve_fixed_point,
    "exact": solve_exact,
    "reference": solve_reference,
}


def solve(c: RAConstants, mask: jnp.ndarray, method: str = "exact") -> RASolution:
    """Solve problem (18). ``method`` in {paper, fixed_point, exact, reference}."""
    return SOLVERS[method](c, mask)
