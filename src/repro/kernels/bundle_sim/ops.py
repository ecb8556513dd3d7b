"""Public jit'd wrapper for the bundle_sim Pallas kernel.

Handles zero-padding to hardware-aligned tiles (zeros are exact identities
for both the dot products and the fused norm reduction: a zero-padded D
contributes nothing; zero-padded bundle rows produce similarity columns that
are sliced away; zero-padded query rows produce garbage rows that are sliced
away)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.bundle_sim.bundle_sim import bundle_sim_pallas


@functools.partial(jax.jit, static_argnames=("block_b", "block_d", "interpret"))
def bundle_similarity(h: jax.Array, m: jax.Array, *, block_b: int = 256,
                      block_d: int = 512,
                      interpret: bool | None = None) -> jax.Array:
    """Cosine similarities of queries against pre-normalized bundles.

    h: (B, D) float (any of f32/bf16); m: (n, D).  Returns (B, n) f32.
    """
    if interpret is None:
        interpret = common.interpret()
    b, d = h.shape
    n = m.shape[0]
    block_b = min(block_b, common.round_up(b, common.sublane(h.dtype)))
    block_d = min(block_d, common.round_up(d, 128))
    hp = common.pad_axis(common.pad_axis(h, 0, block_b), 1, block_d)
    mp = common.pad_axis(common.pad_axis(m, 0, 128), 1, block_d)
    out = bundle_sim_pallas(hp, mp, block_b=block_b, block_d=block_d,
                            interpret=interpret)
    return out[:b, :n]
