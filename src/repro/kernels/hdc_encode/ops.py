"""Public jit'd wrapper for the hdc_encode Pallas kernel.

Returns the fully normalized phi(x) matching repro.hdc.encoders.encode:
    l2n( l2n(nonlin(x W)) - center )
The kernel produces nonlin(xW) per D tile; the two normalizations are
row-wide reductions done here (cheap elementwise passes, fused by XLA).

Padding correctness: F padded with zero features and zero weight rows adds
nothing to z; D padded with zero weight columns yields h=nonlin(0)-0 columns
that are sliced away before normalization (for "cos", nonlin(0)=cos(b)*0=0;
for rp/rp_sign it is 0 as well, and padded center/bias are zeros)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.hdc_encode.hdc_encode import hdc_encode_pallas


def _l2n(v, axis=-1, eps=1e-12):
    return v / (jnp.linalg.norm(v, axis=axis, keepdims=True) + eps)


@functools.partial(jax.jit, static_argnames=("kind", "block_b", "block_d",
                                             "block_f", "interpret"))
def hdc_encode(x: jax.Array, proj: jax.Array, bias: jax.Array,
               center: jax.Array, *, kind: str = "cos", block_b: int = 256,
               block_d: int = 512, block_f: int = 640,
               interpret: bool | None = None) -> jax.Array:
    """Fused encoder: x (B, F), proj (F, D), bias (D,), center (D,) ->
    (B, D) f32, normalized exactly like repro.hdc.encoders.encode."""
    if interpret is None:
        interpret = common.interpret()
    b, f = x.shape
    d = proj.shape[1]
    block_b = min(block_b, common.round_up(b, 8))
    block_d = min(block_d, common.round_up(d, 128))
    block_f = min(block_f, common.round_up(f, 128))
    xp = common.pad_axis(common.pad_axis(x, 0, block_b), 1, block_f)
    wp = common.pad_axis(common.pad_axis(proj, 0, block_f), 1, block_d)
    bp = common.pad_axis(bias[None, :], 1, block_d)
    # kernel subtracts `center` pre-normalization; pass zeros and apply the
    # (normalized-scale) center here to match encoders.encode semantics
    zeros = jnp.zeros_like(bp)
    raw = hdc_encode_pallas(xp, wp, bp, zeros, kind=kind, block_b=block_b,
                            block_d=block_d, block_f=block_f,
                            interpret=interpret)[:b, :d]
    return _l2n(_l2n(raw) - center)
