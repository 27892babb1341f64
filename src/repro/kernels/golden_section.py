"""Batched golden-section fixed-point RA solver — Pallas TPU kernel.

Fuses the whole :func:`repro.core.resource_allocation.solve_fixed_point`
iteration stack — the 2x``n_bracket`` feasible-deadline bisection, the
``n_golden`` golden-section probes each paying an ``n_inner`` beta<->f KKT
fixed point, and the final clip/normalize — into ONE kernel pass over a
block of candidate groups. The XLA path lowers the same math to hundreds of
tiny sequential HLO ops *per group*; here every probe is a VMEM-resident
vector op over the (block_g, R) group block, so the sequential depth is paid
once per block instead of once per group and nothing round-trips HBM between
iterations.

Group constants follow :class:`repro.core.cost_model.RAConstants` leaf
layout batched over groups: ``a, b, d, e, f_min, f_max, mask`` are
``(G, R)`` and ``w`` is ``(G,)`` (one scalar weight per group). The math
mirrors ``solve_fixed_point`` op-for-op, except that the cube root is taken
as ``exp(log(x) / 3)`` (Mosaic cannot lower ``cbrt``). Results agree with
the XLA solver to float32 rounding wherever the cost along the deadline
path is unimodal; where it is not, an ulp-level difference can settle the
golden section in another local minimum, so agreement is a share of the
groups, not a per-group pin (``chip_smoke.py`` checks it on the chip).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_GOLDEN = 0.6180339887498949
_EPS = 1e-12


def _golden_section_kernel(a_ref, b_ref, d_ref, e_ref, w_ref, fmin_ref,
                           fmax_ref, mask_ref, f_ref, beta_ref, cost_ref,
                           dl_ref, *, n_golden: int, n_inner: int,
                           n_bracket: int):
    a = a_ref[...]
    b = b_ref[...]
    d = d_ref[...]
    e = e_ref[...]
    w = w_ref[...]                     # (g, 1)
    f_min = fmin_ref[...]
    f_max = fmax_ref[...]
    mask = mask_ref[...]               # (g, r) bool

    def beta_norm(score):
        score = jnp.where(mask, score, 0.0)
        tot = jnp.maximum(jnp.sum(score, axis=-1, keepdims=True), _EPS)
        return jnp.where(mask, score / tot, 0.0)

    def beta_of_f(f):
        # cube root as exp(log(x)/3): Mosaic has no lowering for cbrt, and
        # x >= _EPS > 0 keeps the log finite
        tau = 2.0 * b * f ** 3 / jnp.maximum(e, _EPS)
        return beta_norm(jnp.exp(jnp.log(jnp.maximum(a + tau * d, _EPS))
                                 / 3.0))

    def safe(beta):
        return jnp.where(mask, jnp.maximum(beta, _EPS), 1.0)

    # ---- feasible deadline bracket: bisect sum_n beta_min(t) <= 1 with
    # every device at f_max (lower end) and at f_min (upper end); both
    # searches run stacked so the depth is n_bracket, not 2x ----
    def bound_hi(fx):
        lo = jnp.max(jnp.where(mask, e / fx + d, 0.0), axis=-1, keepdims=True)
        hi = lo + jnp.sum(jnp.where(mask, d, 0.0), axis=-1,
                          keepdims=True) * 1e4 + 1.0

        def body(_, lohi):
            lo_, hi_ = lohi
            mid = 0.5 * (lo_ + hi_)
            slack = mid - e / fx
            bb = jnp.where(mask, d / jnp.maximum(slack, _EPS), 0.0)
            bb = jnp.where(mask & (slack <= 0), 1e6, bb)
            ok = jnp.sum(bb, axis=-1, keepdims=True) <= 1.0
            return (jnp.where(ok, lo_, mid), jnp.where(ok, mid, hi_))

        _, hi_ = lax.fori_loop(0, n_bracket, body, (lo, hi))
        return hi_

    t_lo = bound_hi(f_max) * (1.0 + 1e-6)                        # (g, 1)
    t_hi = jnp.maximum(bound_hi(f_min) * 1.5, t_lo * 4.0) + 1.0

    def fb_of_t(t):
        def body(_, f):
            slack = t - d / safe(beta_of_f(f))
            f_new = jnp.where(slack > 0, e / jnp.maximum(slack, _EPS), f_max)
            return jnp.clip(f_new, f_min, f_max)

        f = lax.fori_loop(0, n_inner, body, jnp.sqrt(f_min * f_max))
        return f, beta_of_f(f)

    def objective(f, safe_beta):
        per_sum = a / safe_beta + b * jnp.square(f)
        per_max = d / safe_beta + e / f
        return (jnp.sum(jnp.where(mask, per_sum, 0.0), -1, keepdims=True)
                + w * jnp.max(jnp.where(mask, per_max, 0.0), -1,
                              keepdims=True))

    def cost_of_t(t):
        f, beta = fb_of_t(t)
        return objective(f, safe(beta))

    # ---- golden-section over t, single-eval recurrence (G^2 = 1 - G) ----
    m1 = t_hi - _GOLDEN * (t_hi - t_lo)
    m2 = t_lo + _GOLDEN * (t_hi - t_lo)
    c1, c2 = cost_of_t(m1), cost_of_t(m2)

    def gbody(_, st):
        lo, hi, m1, m2, c1, c2 = st
        go_right = c1 > c2
        lo = jnp.where(go_right, m1, lo)
        hi = jnp.where(go_right, hi, m2)
        m1n = hi - _GOLDEN * (hi - lo)
        m2n = lo + _GOLDEN * (hi - lo)
        point = jnp.where(go_right, m2n, m1n)
        cp = cost_of_t(point)
        m1_new = jnp.where(go_right, m2, point)
        c1_new = jnp.where(go_right, c2, cp)
        m2_new = jnp.where(go_right, point, m1)
        c2_new = jnp.where(go_right, cp, c1)
        return lo, hi, m1_new, m2_new, c1_new, c2_new

    lo, hi, *_ = lax.fori_loop(0, n_golden, gbody,
                               (t_lo, t_hi, m1, m2, c1, c2))
    f, beta = fb_of_t(0.5 * (lo + hi))

    # ---- finalize (clip/renormalize; empty groups cost 0) ----
    any_active = jnp.any(mask, axis=-1, keepdims=True)
    f = jnp.where(mask, jnp.clip(f, f_min, f_max), f_min)
    beta = beta_norm(jnp.maximum(beta, _EPS))
    sb = safe(beta)
    f_ref[...] = f
    beta_ref[...] = beta
    cost_ref[...] = jnp.where(any_active, objective(f, sb), 0.0)
    dl_ref[...] = jnp.max(jnp.where(mask, d / sb + e / f, 0.0), -1,
                          keepdims=True)


def _block_rows(r: int) -> int:
    """Groups per kernel block for groups of width ``r``: as many as keep a
    (rows, R) f32 block at 256 x 128 elements once R is padded to whole
    128-lane tiles. At that size the double-buffered blocks and the body's
    temporaries fit the chip's 16 MiB of scoped VMEM; a fixed 256 rows does
    not once R reaches ~1000, the widest reach buckets at N=20k."""
    lanes = -(-r // 128) * 128
    return max(8, 256 * 128 // lanes // 8 * 8)


def golden_section_solve(a, b, d, e, w, f_min, f_max, mask, *,
                         n_golden: int = 48, n_inner: int = 12,
                         n_bracket: int = 60, block_g: int | None = None,
                         interpret: bool = False):
    """Solve G groups of problem (18) at once along the KKT deadline path.

    ``a, b, d, e, f_min, f_max, mask``: (G, R); ``w``: (G,). Returns
    ``(f (G, R), beta (G, R), cost (G,), deadline (G,))``. ``block_g``
    defaults to :func:`_block_rows` of R.
    """
    g, r = a.shape
    block_g = _block_rows(r) if block_g is None else block_g
    block_g = max(min(block_g, g), 1)
    pad = (-g) % block_g

    def pad2(x, value=0.0):
        x = jnp.asarray(x)
        if not pad:
            return x
        return jnp.pad(x, ((0, pad), (0, 0)), constant_values=value)

    # padded rows get benign all-masked-out groups: unit constants keep the
    # bracket/fixed-point arithmetic finite, mask=False keeps them inert
    a2, b2, d2 = pad2(a, 1.0), pad2(b, 1.0), pad2(d, 1.0)
    e2, fmin2, fmax2 = pad2(e, 1.0), pad2(f_min, 1.0), pad2(f_max, 1.0)
    mask2 = pad2(mask.astype(bool), False)
    w2 = jnp.asarray(w, a2.dtype).reshape(g, 1)
    if pad:
        w2 = jnp.pad(w2, ((0, pad), (0, 0)))
    g2 = g + pad
    n_blocks = g2 // block_g

    row_spec = pl.BlockSpec((block_g, r), lambda i: (i, 0))
    one_spec = pl.BlockSpec((block_g, 1), lambda i: (i, 0))
    f, beta, cost, dl = pl.pallas_call(
        functools.partial(_golden_section_kernel, n_golden=n_golden,
                          n_inner=n_inner, n_bracket=n_bracket),
        grid=(n_blocks,),
        in_specs=[row_spec] * 4 + [one_spec] + [row_spec] * 3,
        out_specs=[row_spec, row_spec, one_spec, one_spec],
        out_shape=[
            jax.ShapeDtypeStruct((g2, r), a2.dtype),
            jax.ShapeDtypeStruct((g2, r), a2.dtype),
            jax.ShapeDtypeStruct((g2, 1), a2.dtype),
            jax.ShapeDtypeStruct((g2, 1), a2.dtype),
        ],
        interpret=interpret,
    )(a2, b2, d2, e2, w2, fmin2, fmax2, mask2)
    return (f[:g], beta[:g], cost[:g, 0], dl[:g, 0])
