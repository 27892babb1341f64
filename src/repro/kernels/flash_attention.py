"""Flash attention — Pallas TPU kernel (forward) with online softmax.

TPU adaptation (not a CUDA port): the kernel exploits the sequential
execution of the trailing grid axis on TPU — the KV axis is the innermost
grid dimension and the running (acc, m, l) statistics live in VMEM scratch
that persists across those sequential steps. Tiles are MXU-aligned
(block_q x head_dim and block_kv x head_dim with head_dim a multiple of
128 on real configs); softmax statistics are f32 regardless of input dtype.

Layout inside the kernel: (B, H, S, hd). GQA is handled by the k/v
BlockSpec index maps (q head h reads kv head h // group).

Causal handling: fully-masked kv tiles are skipped via ``pl.when`` (the
triangle schedule); the diagonal tile applies the position mask.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                causal: bool, block_q: int, block_kv: int, n_kv: int,
                scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    kv_start = ik * block_kv
    # last kv tile this q tile can see (inclusive), for the final write
    if causal:
        last_ik = jnp.minimum((q_start + block_q - 1) // block_kv, n_kv - 1)
        visible = kv_start <= q_start + block_q - 1
    else:
        last_ik = n_kv - 1
        visible = True

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                # (bkv, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            kv_pos = kv_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(kv_pos <= q_pos, s, _NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == last_ik)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, block_q: int = 512,
                        block_kv: int = 512,
                        softmax_scale: float | None = None,
                        interpret: bool = False):
    """q: (B, Sq, Hq, hd); k, v: (B, Skv, Hkv, hd) -> (B, Sq, Hq, hd)."""
    b, sq, hq, hd = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    assert sq % block_q == 0 and skv % block_kv == 0
    nq, nk = sq // block_q, skv // block_kv

    # kernel layout (B, H, S, hd)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_fwd_kernel, causal=causal, block_q=block_q,
                               block_kv=block_kv, n_kv=nk, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda b_, h, iq, ik: (b_, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda b_, h, iq, ik: (b_, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b_, h, iq, ik: (b_, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
