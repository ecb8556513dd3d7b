"""Public jit'd wrapper for the profile_decode Pallas kernel.

The kernel reads the (C, n) profile table as its (n, C) transpose, which is
a bitcast where the table is stored class-minor (as XLA lays out a table
with a small n on the TPU), so the table is neither copied nor padded per
call.  Only the activations are padded, on B, with rows that are sliced
away."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.profile_decode.profile_decode import profile_decode_pallas

# The double-buffered profile and score blocks of one grid step stay under
# this, inside the default scoped VMEM with room for the in-kernel temporaries.
_VMEM_BLOCKS_BYTES = 8 << 20
# Beyond this many lanes the per-step overhead is already small.
_MAX_BLOCK_C = 8192


def _block_c(c: int, n: int, block_b: int, dtype) -> int:
    """Widest C tile (a multiple of 128 lanes, or all of C) whose
    double-buffered (n, bc) profile block and (bm, bc) f32 score block fit
    the budget."""
    lane_bytes = 2 * (common.round_up(n, common.sublane(dtype))
                      * jnp.dtype(dtype).itemsize + block_b * 4)
    fit = _VMEM_BLOCKS_BYTES // lane_bytes // 128 * 128
    block_c = max(128, min(fit, _MAX_BLOCK_C))
    return c if c <= block_c else block_c


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def profile_decode_scores(acts: jax.Array, profiles: jax.Array, *,
                          block_b: int = 256,
                          interpret: bool | None = None) -> jax.Array:
    """-||A - P_c||^2 decode scores.  acts (B, n), profiles (C, n) -> (B, C)."""
    if interpret is None:
        interpret = common.interpret()
    b, n = acts.shape
    c = profiles.shape[0]
    block_b = min(block_b, common.round_up(b, common.sublane(acts.dtype)))
    block_c = _block_c(c, n, block_b, profiles.dtype)
    out = profile_decode_pallas(common.pad_axis(acts, 0, block_b), profiles.T,
                                block_b=block_b, block_c=block_c,
                                interpret=interpret)
    return out[:b]
