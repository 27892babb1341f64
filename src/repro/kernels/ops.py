"""Public jit'd wrappers around the Pallas kernels.

On non-TPU backends (this container is CPU) the kernels run in
``interpret=True`` mode — the kernel body executes in Python/XLA for
correctness validation; on TPU they compile to Mosaic. ``flash_attention``
is differentiable via custom_vjp: the forward is the Pallas kernel, the
backward recomputes through the reference formulation (flash-style
recompute — no (Sq, Skv) residuals are stored).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.golden_section import \
    golden_section_solve as _golden_section_solve
from repro.kernels.hier_aggregate import hier_aggregate as _hier_aggregate
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm
from repro.kernels.ssd_scan import ssd_state_scan as _ssd_state_scan


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                    block_kv: int = 512):
    return flash_attention_fwd(q, k, v, causal=causal, block_q=block_q,
                               block_kv=block_kv, interpret=not _on_tpu())


def _fa_fwd(q, k, v, causal, block_q, block_kv):
    out = flash_attention(q, k, v, causal, block_q, block_kv)
    return out, (q, k, v)


def _fa_bwd(causal, block_q, block_kv, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _ref.flash_attention_ref(q_, k_, v_,
                                                    causal=causal), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def rmsnorm(x, scale, *, eps: float = 1e-6, block_rows: int = 256):
    return _rmsnorm(x, scale, eps=eps, block_rows=block_rows,
                    interpret=not _on_tpu())


def hier_aggregate(updates, weights, *, block_p: int = 65_536):
    return _hier_aggregate(updates, weights, block_p=block_p,
                           interpret=not _on_tpu())


def hier_aggregate_tree(trees: list, weights):
    """Weighted-average a list of pytrees through the fused kernel."""
    flat = [jnp.concatenate([leaf.reshape(-1) for leaf in jax.tree.leaves(t)])
            for t in trees]
    stacked = jnp.stack(flat)
    merged = hier_aggregate(stacked, jnp.asarray(weights))
    # unflatten back into the first tree's structure
    leaves, treedef = jax.tree.flatten(trees[0])
    out, off = [], 0
    for leaf in leaves:
        out.append(merged[off:off + leaf.size].reshape(leaf.shape)
                   .astype(leaf.dtype))
        off += leaf.size
    return jax.tree.unflatten(treedef, out)


def golden_section_solve(a, b, d, e, w, f_min, f_max, mask, *,
                         n_golden: int = 48, n_inner: int = 12,
                         n_bracket: int = 60, block_g: int | None = None):
    """Batched fused golden-section RA solve; see
    :mod:`repro.kernels.golden_section` for shapes."""
    return _golden_section_solve(a, b, d, e, w, f_min, f_max, mask,
                                 n_golden=n_golden, n_inner=n_inner,
                                 n_bracket=n_bracket, block_g=block_g,
                                 interpret=not _on_tpu())


def ssd_state_scan(states, decay, initial_state=None):
    return _ssd_state_scan(states, decay, initial_state,
                           interpret=not _on_tpu())
