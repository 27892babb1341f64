"""Batched KKT group solve of the fast kind — Pallas TPU kernel.

Runs :func:`repro.kernels.kkt.kkt_rows` — the bisection of the
least feasible deadline, the golden section over the deadline, the Newton
search of the bandwidth price at each probe and each device's Newton root
at each price, and the final clip/normalize — as ONE kernel pass over a
block of candidate groups. The XLA path lowers the same math to hundreds of
tiny sequential HLO ops *per group*; here every step is a VMEM-resident
vector op over the (block_g, R) group block, so the sequential depth is
paid once per block instead of once per group and nothing round-trips HBM
between iterations.

Group constants follow :class:`repro.core.cost_model.RAConstants` leaf
layout batched over groups: ``a, b, d, e, f_min, f_max, mask`` are
``(G, R)`` and ``w`` is ``(G,)`` (one scalar weight per group). The math is
the XLA solver's own function (it uses ``sqrt``, ``log`` and ``exp``, which
Mosaic lowers), so results agree with the XLA solver to float32 rounding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.kkt import kkt_rows


def _golden_section_kernel(a_ref, b_ref, d_ref, e_ref, w_ref, fmin_ref,
                           fmax_ref, mask_ref, f_ref, beta_ref, cost_ref,
                           dl_ref, **iters):
    f, beta, cost, deadline = kkt_rows(
        a_ref[...], b_ref[...], d_ref[...], e_ref[...], w_ref[...],
        fmin_ref[...], fmax_ref[...], mask_ref[...], **iters)
    f_ref[...] = f
    beta_ref[...] = beta
    cost_ref[...] = cost
    dl_ref[...] = deadline


def _block_rows(r: int) -> int:
    """Groups per kernel block for groups of width ``r``: as many as keep a
    (rows, R) f32 block at 256 x 128 elements once R is padded to whole
    128-lane tiles. At that size the double-buffered blocks and the body's
    temporaries fit the chip's 16 MiB of scoped VMEM; a fixed 256 rows does
    not once R reaches ~1000, the widest reach buckets at N=20k."""
    lanes = -(-r // 128) * 128
    return max(8, 256 * 128 // lanes // 8 * 8)


def golden_section_solve(a, b, d, e, w, f_min, f_max, mask, *,
                         n_golden: int = 24, n_price: int = 5,
                         n_root: int = 5, n_bracket: int = 24,
                         block_g: int | None = None,
                         interpret: bool = False):
    """Solve G groups of problem (18) at once from their KKT conditions.

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
    # arithmetic finite, mask=False keeps them inert
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
                          n_price=n_price, n_root=n_root,
                          n_bracket=n_bracket),
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
