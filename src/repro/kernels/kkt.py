"""The optimum of problem (18) from its KKT conditions, as plain jnp.

One function, :func:`kkt_rows`, prices groups of devices laid out along the
last axis. The fast kind's XLA solver
(:func:`repro.core.resource_allocation.solve_fixed_point`), the Pallas
kernel (:mod:`repro.kernels.golden_section`) and its mirror
(:func:`repro.kernels.ref.golden_section_ref`) all call it, so the three
share one statement of the mathematics. It depends on nothing of
``repro.core``: the solvers there import it, not the other way round.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

GOLDEN = 0.6180339887498949
EPS = 1e-12


def golden_min(fn, lo, hi, n_iter: int):
    """Golden-section minimize with the classic single-eval recurrence.

    Each iteration shrinks the bracket by the golden ratio while evaluating
    ``fn`` ONCE (the surviving interior probe is reused via G^2 = 1 - G),
    instead of the two evaluations per iteration of the naive form — the
    dominant sequential-depth cost of every solver of (18). ``lo``/``hi``
    may be arrays (vectorized independent searches); returns the bracket
    midpoint.
    """
    m1 = hi - GOLDEN * (hi - lo)
    m2 = lo + GOLDEN * (hi - lo)
    c1, c2 = fn(m1), fn(m2)

    def body(_, st):
        lo, hi, m1, m2, c1, c2 = st
        go_right = c1 > c2
        lo = jnp.where(go_right, m1, lo)
        hi = jnp.where(go_right, hi, m2)
        m1n = hi - GOLDEN * (hi - lo)
        m2n = lo + GOLDEN * (hi - lo)
        # the surviving probe becomes the opposite interior point; only the
        # freshly exposed point needs an evaluation
        point = jnp.where(go_right, m2n, m1n)
        cp = fn(point)
        m1_new = jnp.where(go_right, m2, point)
        c1_new = jnp.where(go_right, c2, cp)
        m2_new = jnp.where(go_right, point, m1)
        c2_new = jnp.where(go_right, cp, c1)
        return lo, hi, m1_new, m2_new, c1_new, c2_new

    lo, hi, *_ = lax.fori_loop(0, n_iter, body, (lo, hi, m1, m2, c1, c2))
    return 0.5 * (lo + hi)


def _kkt_split(a, b, d, e, f_min, f_max, t, nu, n_root: int):
    """Each device's (f, beta) at deadline ``t`` and bandwidth price ``nu``:
    the least point of  a/beta + b f^2 + nu beta  with d/beta + e/f <= t and
    f, beta in their boxes, plus d beta / d nu for the price search.

    Two cases. A device that finishes by ``t`` at f_min with the share
    beta = min(1, sqrt(a/nu)) is not tight: f = f_min. Otherwise it takes a
    tight pair on d/beta + e/f = t, which leaves one variable, the
    communication time u = d/beta (f = e/(t - u)). In v = ln u its least
    point is the root of the increasing, nearly linear

        psi(v) = 2 v + ln(a/d + 2 b e^2 / (t - u)^3) - ln(nu d)

    on [ln max(d, t - e/f_min), ln(t - e/f_max)] (beta <= 1 and f >= f_min
    below, f <= f_max above), or the end of that interval where psi keeps
    one sign. Since psi(v) >= 2 (v - ln u_a) with u_a = d sqrt(nu/a), the
    Newton steps start at min(ln u_a, right end), where psi >= 0, and psi
    being convex they then descend on the root from above; ``n_root`` of
    them, kept inside a sign bracket (a step that leaves it halves it).
    Returns (f, beta, d beta/d nu). Group scalars ``t``, ``nu`` broadcast
    over the device axis."""
    beta_a = jnp.minimum(jnp.sqrt(a / nu), 1.0)
    free = d / beta_a + e / f_min <= t
    log_nud = jnp.log(nu * d)

    def psi(v):
        u = jnp.exp(v)
        s = t - u                          # computation time
        f = e / s
        q = 2.0 * b * f * f / s
        return (2.0 * v + jnp.log(a / d + q) - log_nud,
                2.0 + 3.0 * q * u / (s * (a / d + q)), f, u)

    lo = jnp.log(jnp.maximum(d, t - e / f_min))
    hi = jnp.log(t - e / f_max)
    at_lo = psi(lo)[0] >= 0.0
    at_hi = psi(hi)[0] <= 0.0

    def newton(_, st):
        v, lo, hi = st
        p, dp = psi(v)[:2]
        lo = jnp.where(p < 0.0, v, lo)
        hi = jnp.where(p > 0.0, v, hi)
        step = v - p / dp
        v = jnp.where((step >= lo) & (step <= hi), step, 0.5 * (lo + hi))
        return v, lo, hi

    v_a = 0.5 * jnp.log(nu / a) + jnp.log(d)
    v, _, _ = lax.fori_loop(0, n_root, newton,
                            (jnp.clip(v_a, lo, hi), lo, hi))
    v = jnp.where(at_lo, lo, jnp.where(at_hi, hi, v))
    _, dp, f, u = psi(v)
    beta_t = d / u
    dbeta_t = jnp.where(at_lo | at_hi, 0.0, -beta_t / (nu * dp))
    f = jnp.where(free, f_min, f)
    beta = jnp.where(free, beta_a, beta_t)
    dbeta = jnp.where(free, jnp.where(beta_a < 1.0, -0.5 * beta_a / nu, 0.0),
                      dbeta_t)
    return f, beta, dbeta


def kkt_rows(a, b, d, e, w, f_min, f_max, mask, *, n_golden: int,
             n_price: int, n_root: int, n_bracket: int):
    """The optimum of problem (18) for groups laid out along the last axis:
    constants ``(..., R)``, ``w`` broadcastable to ``(..., 1)``, ``mask``
    ``(..., R)``. Returns ``(f, beta, cost, deadline)``; ``cost`` and
    ``deadline`` keep a trailing axis of 1 and an empty group costs 0.

    (18) is convex. With the common deadline t fixed it separates by device
    once the share sum is priced at nu (:func:`_kkt_split`), and nu is the
    root of  ln sum beta(nu) = 0  in ln nu: ``n_price`` Newton steps inside
    a sign bracket. The bracket comes from target shares that sum to 1: the
    least feasible share of each device plus the spare split as sqrt(a); at
    the least of the devices' prices for their targets every share is at
    least its target, at the largest at most. The objective over t is
    convex, and golden section (``n_golden`` steps) finds its least point
    between the least feasible deadline (``n_bracket`` bisection steps) and
    the deadline of the unconstrained optimum (f = f_min, beta ~ sqrt(a)),
    past which it only grows. Dependent depth: (n_golden + 3) probes of
    (n_price + 1) device solves of (n_root + 2) steps, plus n_bracket:
    1,158 steps at the default profile.

    Plain jnp with keepdims reductions, so it runs per group (R,), over a
    batch (G, R), and inside the Pallas kernel
    (:mod:`repro.kernels.golden_section`) alike."""
    red = dict(axis=-1, keepdims=True)

    def total(x):
        return jnp.sum(jnp.where(mask, x, 0.0), **red)

    e_fx = e / f_max
    lo = jnp.max(jnp.where(mask, e_fx + d, 0.0), **red)
    hi = jnp.max(jnp.where(mask, e_fx, 0.0), **red) + total(d)

    def bisect(_, lohi):
        lo_, hi_ = lohi
        mid = 0.5 * (lo_ + hi_)
        slack = mid - e_fx
        need = total(jnp.where(slack > 0.0, d / slack, jnp.inf))
        ok = need <= 1.0
        return jnp.where(ok, lo_, mid), jnp.where(ok, mid, hi_)

    _, t_lo = lax.fori_loop(0, n_bracket, bisect, (lo, hi))
    root_a = jnp.where(mask, jnp.sqrt(a), 0.0)
    sum_root_a = jnp.sum(root_a, **red)
    t_free = jnp.max(jnp.where(mask, d * sum_root_a / jnp.sqrt(a)
                               + e / f_min, 0.0), **red)
    t_lo = t_lo * (1.0 + 1e-6)
    t_hi = jnp.maximum(t_free, t_lo)

    def alloc(t):
        u_hi = t - e_fx
        beta_min = d / u_hi
        spare = jnp.maximum(1.0 - total(beta_min), 0.0)
        target = beta_min + spare * root_a / sum_root_a
        # the price at which each device takes its target share
        s = t - d / target
        f_t = e / s
        tail = jnp.where(f_t > f_min, 2.0 * b * f_t * f_t * d / s, 0.0)
        price = (a + tail) / (target * target)
        y_lo = jnp.log(jnp.min(jnp.where(mask, price, jnp.inf), **red))
        y_hi = jnp.log(jnp.max(jnp.where(mask, price, 0.0), **red))

        def newton(_, st):
            y, y_lo, y_hi = st
            nu = jnp.exp(y)
            _, beta, dbeta = _kkt_split(a, b, d, e, f_min, f_max, t, nu,
                                        n_root)
            g_sum = total(beta)
            g = jnp.log(g_sum)                 # decreasing in y
            dg = nu * total(dbeta) / g_sum
            y_lo = jnp.where(g > 0.0, y, y_lo)
            y_hi = jnp.where(g < 0.0, y, y_hi)
            step = y - g / dg
            y = jnp.where((step >= y_lo) & (step <= y_hi), step,
                          0.5 * (y_lo + y_hi))
            return y, y_lo, y_hi

        y, _, _ = lax.fori_loop(0, n_price, newton,
                                (0.5 * (y_lo + y_hi), y_lo, y_hi))
        f, beta, _ = _kkt_split(a, b, d, e, f_min, f_max, t, jnp.exp(y),
                                n_root)
        return f, beta / total(beta)

    def objective(f, beta):
        safe = jnp.where(mask, beta, 1.0)
        return (total(a / safe + b * f * f)
                + w * jnp.max(jnp.where(mask, d / safe + e / f, 0.0), **red))

    t = golden_min(lambda t: objective(*alloc(t)), t_lo, t_hi, n_golden)
    f, beta = alloc(t)
    any_active = jnp.any(mask, **red)
    f = jnp.where(mask, jnp.clip(f, f_min, f_max), f_min)
    beta = jnp.where(mask, jnp.maximum(beta, EPS), 0.0)
    beta = beta / jnp.maximum(jnp.sum(beta, **red), EPS)
    safe = jnp.where(mask, beta, 1.0)
    cost = jnp.where(any_active, objective(f, beta), 0.0)
    deadline = jnp.max(jnp.where(mask, d / safe + e / f, 0.0), **red)
    return f, beta, cost, deadline
