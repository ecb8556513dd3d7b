"""Public jit'd wrapper for the fused LogHD LM head kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.loghd_head.loghd_head import loghd_head_pallas


@functools.partial(jax.jit, static_argnames=("block_b", "block_v", "block_d",
                                             "interpret"))
def loghd_head_logits(h: jax.Array, m: jax.Array, p: jax.Array, *,
                      block_b: int = 256, block_v: int = 1024,
                      block_d: int = 512,
                      interpret: bool | None = None) -> jax.Array:
    """Fused LogHD vocab head: h (B, D) hidden states, m (n, D) bundles,
    p (V, n) vocab profiles -> (B, V) f32 logits = -||h M^T - P_v||^2.

    Padding correctness: zero-padded D contributes nothing to A; zero-padded
    n contributes zeros to dots and norms; padded V rows are sliced away;
    padded B rows are sliced away."""
    if interpret is None:
        interpret = common.interpret()
    b, d = h.shape
    n = m.shape[0]
    v = p.shape[0]
    block_b = min(block_b, common.round_up(b, common.sublane(h.dtype)))
    block_v = min(block_v, common.round_up(v, 128))
    block_d = min(block_d, common.round_up(d, 128))
    n_pad = common.round_up(n, 128)
    hp = common.pad_axis(common.pad_axis(h, 0, block_b), 1, block_d)
    mp = common.pad_axis(common.pad_axis(m, 0, n_pad), 1, block_d)
    pp = common.pad_axis(common.pad_axis(p, 0, block_v), 1, n_pad)
    out = loghd_head_pallas(hp, mp, pp, block_b=block_b, block_v=block_v,
                            block_d=block_d, interpret=interpret)
    return out[:b, :v]
