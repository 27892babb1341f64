"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        softmax_scale: float | None = None):
    """Naive attention. q: (B, Sq, Hq, hd); k, v: (B, Skv, Hkv, hd)."""
    b, sq, hq, hd = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    qr = q.reshape(b, sq, hkv, g, hd).astype(jnp.float32) * scale
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qr, k.astype(jnp.float32))
    if causal:
        mask = jnp.tril(jnp.ones((sq, skv), bool), k=skv - sq)
        s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32))
    return o.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, hd).astype(q.dtype)


def rmsnorm_ref(x, scale, *, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def hier_aggregate_ref(updates, weights):
    """Weighted average over the leading client axis — eq. (8)/(14).

    updates: (C, P); weights: (C,). Returns (P,) in updates.dtype.
    """
    w = weights.astype(jnp.float32)
    w = w / jnp.maximum(jnp.sum(w), 1e-30)
    return jnp.einsum("c,cp->p", w,
                      updates.astype(jnp.float32)).astype(updates.dtype)


def golden_section_ref(a, b, d, e, w, f_min, f_max, mask, *,
                       n_golden: int = 48, n_inner: int = 12,
                       n_bracket: int = 60):
    """Batched KKT-path RA solve — the plain-jnp mirror of the fused
    golden-section kernel (and of ``solve_fixed_point`` vmapped over
    groups). Constants (G, R), ``w`` (G,); returns (f, beta, cost, deadline)
    with cost/deadline shaped (G,).
    """
    golden = 0.6180339887498949
    eps = 1e-12
    mask = jnp.asarray(mask, bool)
    w = jnp.asarray(w)[:, None]

    def beta_norm(score):
        score = jnp.where(mask, score, 0.0)
        tot = jnp.maximum(jnp.sum(score, axis=-1, keepdims=True), eps)
        return jnp.where(mask, score / tot, 0.0)

    def beta_of_f(f):
        # the kernel's cube root, exp(log(x)/3), op for op
        tau = 2.0 * b * f ** 3 / jnp.maximum(e, eps)
        return beta_norm(jnp.exp(jnp.log(jnp.maximum(a + tau * d, eps))
                                 / 3.0))

    def safe(beta):
        return jnp.where(mask, jnp.maximum(beta, eps), 1.0)

    def bound_hi(fx):
        lo = jnp.max(jnp.where(mask, e / fx + d, 0.0), -1, keepdims=True)
        hi = lo + jnp.sum(jnp.where(mask, d, 0.0), -1,
                          keepdims=True) * 1e4 + 1.0

        def body(_, lohi):
            lo_, hi_ = lohi
            mid = 0.5 * (lo_ + hi_)
            slack = mid - e / fx
            bb = jnp.where(mask, d / jnp.maximum(slack, eps), 0.0)
            bb = jnp.where(mask & (slack <= 0), 1e6, bb)
            ok = jnp.sum(bb, -1, keepdims=True) <= 1.0
            return (jnp.where(ok, lo_, mid), jnp.where(ok, mid, hi_))

        return jax.lax.fori_loop(0, n_bracket, body, (lo, hi))[1]

    t_lo = bound_hi(f_max) * (1.0 + 1e-6)
    t_hi = jnp.maximum(bound_hi(f_min) * 1.5, t_lo * 4.0) + 1.0

    def fb_of_t(t):
        def body(_, f):
            slack = t - d / safe(beta_of_f(f))
            f_new = jnp.where(slack > 0, e / jnp.maximum(slack, eps), f_max)
            return jnp.clip(f_new, f_min, f_max)

        f = jax.lax.fori_loop(0, n_inner, body, jnp.sqrt(f_min * f_max))
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

    m1 = t_hi - golden * (t_hi - t_lo)
    m2 = t_lo + golden * (t_hi - t_lo)
    c1, c2 = cost_of_t(m1), cost_of_t(m2)

    def gbody(_, st):
        lo, hi, m1, m2, c1, c2 = st
        go_right = c1 > c2
        lo = jnp.where(go_right, m1, lo)
        hi = jnp.where(go_right, hi, m2)
        m1n = hi - golden * (hi - lo)
        m2n = lo + golden * (hi - lo)
        point = jnp.where(go_right, m2n, m1n)
        cp = cost_of_t(point)
        return (lo, hi,
                jnp.where(go_right, m2, point), jnp.where(go_right, point, m1),
                jnp.where(go_right, c2, cp), jnp.where(go_right, cp, c1))

    lo, hi, *_ = jax.lax.fori_loop(0, n_golden, gbody,
                                   (t_lo, t_hi, m1, m2, c1, c2))
    f, beta = fb_of_t(0.5 * (lo + hi))

    any_active = jnp.any(mask, -1, keepdims=True)
    f = jnp.where(mask, jnp.clip(f, f_min, f_max), f_min)
    beta = beta_norm(jnp.maximum(beta, eps))
    sb = safe(beta)
    cost = jnp.where(any_active, objective(f, sb), 0.0)
    deadline = jnp.max(jnp.where(mask, d / sb + e / f, 0.0), -1,
                       keepdims=True)
    return f, beta, cost[:, 0], deadline[:, 0]


def ssd_state_scan_ref(states, decay, initial_state=None):
    """Inter-chunk SSD recurrence.

    states: (NC, B, H, N, P) per-chunk accumulated states;
    decay:  (NC, B, H) per-chunk total decay.
    Returns (entering (NC, B, H, N, P), final (B, H, N, P)) where
    ``entering[c]`` is the carried state at the START of chunk c.
    """
    nc, b, h, n, p = states.shape
    init = (jnp.zeros((b, h, n, p), jnp.float32) if initial_state is None
            else initial_state.astype(jnp.float32))

    def body(carry, xs):
        st, dec = xs
        new = carry * dec.astype(jnp.float32)[..., None, None] + \
            st.astype(jnp.float32)
        return new, carry

    final, entering = jax.lax.scan(body, init,
                                   (states.astype(jnp.float32),
                                    decay.astype(jnp.float32)))
    return entering.astype(states.dtype), final.astype(states.dtype)
