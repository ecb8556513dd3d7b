"""Pallas TPU kernel: nearest-profile decode scores.

Computes scores[b, c] = -||A_b - P_c||^2 expanded as
    2 <A_b, P_c> - ||P_c||^2 - ||A_b||^2
which keeps the argmax semantics of Eq. 7 while turning the decode into one
(bm, n) x (n, bc) MXU matmul plus rank-1 biases — the streaming form of the
ASIC's decode stage (paper Fig. 2c).

  * the profiles are read class-minor, as the (n, C) transpose of the stored
    (C, n) table: a block is (n, bc), n whole (no padding to 128 lanes), so
    each step streams n rows of bc contiguous classes,
  * grid = (B tiles, cdiv(C, bc)); the last C tile is ragged.  Its lanes
    past C compute values that are never stored, and each score column
    depends only on its own profile column, so nothing leaks into real ones,
  * ||P_c||^2 (a sum over sublanes) and ||A_b||^2 are computed in-block
    (cheap: O(bc*n), O(bm*n)), so profiles are read from HBM exactly once
    per B tile,
  * used at classifier scale (C <= a few hundred, one tile) and at extreme
    scale (C = 1.3M) where the C grid axis does the heavy tiling.

VMEM per step (double-buffered, f32, n rounded up to 8 sublanes):
2 * bc * 4 * (round_up(n, 8) + bm), plus the (bm, n) activations; at
bm=64, n=21, bc=8192: 2 * 8192 * 4 * (24 + 64) ~= 5.8 MB.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(a_ref, p_ref, out_ref):
    a = a_ref[...].astype(jnp.float32)                     # (bm, n)
    p = p_ref[...].astype(jnp.float32)                     # (n, bc)
    dots = jax.lax.dot_general(
        a, p, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # (bm, bc)
    p_sq = jnp.sum(p * p, axis=0)[None, :]                 # (1, bc)
    a_sq = jnp.sum(a * a, axis=1)[:, None]                 # (bm, 1)
    out_ref[...] = (2.0 * dots - p_sq - a_sq).astype(out_ref.dtype)


def profile_decode_pallas(acts: jax.Array, profiles_t: jax.Array, *,
                          block_b: int, block_c: int,
                          interpret: bool = True) -> jax.Array:
    """acts: (B, n), profiles_t: (n, C); returns (B, C) f32 scores.
    B must be a multiple of block_b (ops.py pads it); C need not be a
    multiple of block_c."""
    b, n = acts.shape
    n2, c = profiles_t.shape
    assert n == n2
    assert b % block_b == 0

    return pl.pallas_call(
        _kernel,
        grid=(b // block_b, pl.cdiv(c, block_c)),
        in_specs=[
            pl.BlockSpec((block_b, n), lambda i, j: (i, 0)),
            pl.BlockSpec((n, block_c), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, c), jnp.float32),
        interpret=interpret,
    )(acts, profiles_t)
