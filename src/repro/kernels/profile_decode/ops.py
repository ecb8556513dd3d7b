"""Public jit'd wrapper for the profile_decode Pallas kernel.

Zero-padding correctness: padding the n axis with zeros adds zero to the
dots and the square-norm biases; padding C adds score columns that are
sliced away; padding B adds rows that are sliced away."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.profile_decode.profile_decode import profile_decode_pallas


@functools.partial(jax.jit, static_argnames=("block_b", "block_c", "interpret"))
def profile_decode_scores(acts: jax.Array, profiles: jax.Array, *,
                          block_b: int = 256, block_c: int = 512,
                          interpret: bool | None = None) -> jax.Array:
    """-||A - P_c||^2 decode scores.  acts (B, n), profiles (C, n) -> (B, C)."""
    if interpret is None:
        interpret = common.interpret()
    b, n = acts.shape
    c = profiles.shape[0]
    block_b = min(block_b, common.round_up(b, common.sublane(acts.dtype)))
    block_c = min(block_c, common.round_up(c, 128))
    n_pad = common.round_up(n, 128)
    ap = common.pad_axis(common.pad_axis(acts, 0, block_b), 1, n_pad)
    pp = common.pad_axis(common.pad_axis(profiles, 0, block_c), 1, n_pad)
    out = profile_decode_pallas(ap, pp, block_b=block_b, block_c=block_c,
                                interpret=interpret)
    return out[:b, :c]
