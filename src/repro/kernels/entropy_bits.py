"""Pallas TPU kernel: fused per-pair optimal description cost (Eq. 11/12).

Elementwise but transcendental-heavy (two log2 per element + select); fusing
the entropy + explicit-bits min into one VMEM pass avoids three HBM round
trips in the evaluation path that runs over the full pair table (length |E|)
every iteration. Tiled 1-D over 8·128-aligned blocks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 8 * 128


def _pair_cost_kernel(scal_ref, cnt_ref, pi_ref, out_ref):
    cbar = scal_ref[0]
    log2v = scal_ref[1]
    cnt = cnt_ref[...]
    pi = pi_ref[...]
    safe_pi = jnp.maximum(pi, 1.0)
    sigma = jnp.clip(cnt / safe_pi, 0.0, 1.0)
    xlogx = jnp.where(sigma > 0.0, sigma * jnp.log2(jnp.maximum(sigma, 1e-38)), 0.0)
    ylogy = jnp.where(
        sigma < 1.0, (1.0 - sigma) * jnp.log2(jnp.maximum(1.0 - sigma, 1e-38)), 0.0
    )
    ent = jnp.where((pi > 0.0) & (cnt > 0.0) & (cnt < pi), -pi * (xlogx + ylogy), 0.0)
    out = jnp.where(cnt > 0.0, jnp.minimum(cbar + ent, 2.0 * cnt * log2v), 0.0)
    out_ref[...] = out


def pair_cost_pallas(
    cnt: jax.Array, pi: jax.Array, cbar: jax.Array, log2v: jax.Array,
    *, interpret: bool,
) -> jax.Array:
    """1-D tiled fused pair cost; pads to a BLOCK multiple internally."""
    (e,) = cnt.shape
    pad = (-e) % BLOCK
    cnt_p = jnp.pad(cnt.astype(jnp.float32), (0, pad))
    pi_p = jnp.pad(pi.astype(jnp.float32), (0, pad))
    scal = jnp.stack([cbar.astype(jnp.float32), log2v.astype(jnp.float32)])
    n_blocks = (e + pad) // BLOCK
    out = pl.pallas_call(
        _pair_cost_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((2,), lambda i: (0,)),
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((BLOCK,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((e + pad,), jnp.float32),
        interpret=interpret,
    )(scal, cnt_p, pi_p)
    return out[:e]
