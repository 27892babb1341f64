"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.kkt import kkt_rows


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
                       n_golden: int = 24, n_price: int = 5,
                       n_root: int = 5, n_bracket: int = 24):
    """Batched KKT group solve — the plain-jnp mirror of the fused kernel:
    the kernel body's function applied to the whole (G, R) batch at once,
    with no blocks, no padding and no Pallas. Constants (G, R), ``w`` (G,);
    returns (f, beta, cost, deadline) with cost/deadline shaped (G,).
    """
    f, beta, cost, deadline = kkt_rows(
        a, b, d, e, jnp.asarray(w)[:, None], f_min, f_max,
        jnp.asarray(mask, bool), n_golden=n_golden, n_price=n_price,
        n_root=n_root, n_bracket=n_bracket)
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
