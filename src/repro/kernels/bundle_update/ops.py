"""Public jit'd wrapper for the bundle_update Pallas kernel.

Handles zero-padding to hardware-aligned tiles and the normalization
epilogue.  Zeros are exact identities everywhere: zero-padded batch rows
(of c and h) contribute nothing to the contraction; zero-padded D columns
of m/h produce zero update columns that neither perturb the row norms nor
survive the final slice; zero-padded bundle rows (m rows + c columns)
produce zero rows that are sliced away.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.bundle_update.bundle_update import bundle_update_pallas


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def bundle_update(m: jax.Array, c: jax.Array, h: jax.Array, lr, *,
                  block_d: int = 512,
                  interpret: bool | None = None) -> jax.Array:
    """L2-normalized scatter-add update: l2n(m + lr * c^T h).

    m: (n, D) bundles/prototypes; c: (B, n) per-example coefficients;
    h: (B, D) encoded queries; lr: scalar (traced — folded into c, so
    sweeping it never retraces).  Returns (n, D) f32.
    """
    if interpret is None:
        interpret = common.interpret()
    n, d = m.shape
    b = h.shape[0]
    block_d = min(block_d, common.round_up(d, 128))
    cs = (c * lr).astype(jnp.float32)
    mp = common.pad_axis(common.pad_axis(m.astype(jnp.float32), 0, 128),
                         1, block_d)
    cp = common.pad_axis(common.pad_axis(cs, 0, common.sublane(cs.dtype)),
                         1, 128)
    hp = common.pad_axis(common.pad_axis(h.astype(jnp.float32), 0,
                                         common.sublane(jnp.float32)),
                         1, block_d)
    u, ss = bundle_update_pallas(mp, cp, hp, block_d=block_d,
                                 interpret=interpret)
    norm = jnp.sqrt(ss[:, :1])                       # (n_pad, 1)
    return (u / (norm + 1e-12))[:n, :d]
