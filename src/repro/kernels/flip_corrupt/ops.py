"""Public jit'd wrapper for the flip_corrupt Pallas kernel.

Flattens a QTensor's codes to 2D, zero-pads to hardware-aligned tiles
(padded elements are corrupted garbage and sliced away; their hash indices
may alias real elements', which is harmless since each output depends only
on its own index), and dispatches the fused corrupt+dequantize kernel.

``vmap`` over p and seed (the fault-sweep engine nests two: p-grid outside,
trial keys inside) becomes the kernel's draw axis through a custom batching
rule: every vmapped (p, seed) pair is one draw of ONE kernel call over the
shared codes.  Plain batching of the single-draw call would need a batched
SMEM scalar block, which Mosaic refuses.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.flip_corrupt.flip_corrupt import flip_corrupt_pallas


@functools.lru_cache(maxsize=None)
def _draws(bits: int, true_c: int, block_r: int, block_c: int,
           interpret: bool, use_pltpu_prng: bool):
    """(codes (R, C), scale (1,), p (T,), seed (T,)) -> (T, R, C), with a
    vmap rule that folds batched p/seed into the draw axis."""

    @jax.custom_batching.custom_vmap
    def draws(codes, scale, p, seed):
        return flip_corrupt_pallas(codes, scale, p, seed, bits=bits,
                                   true_c=true_c, block_r=block_r,
                                   block_c=block_c,
                                   use_pltpu_prng=use_pltpu_prng,
                                   interpret=interpret)

    @draws.def_vmap
    def _rule(axis_size, in_batched, codes, scale, p, seed):
        if in_batched[0] or in_batched[1]:
            # a batch of different stored words: one call per member
            args = tuple(
                a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip((codes, scale, p, seed), in_batched))
            return jax.lax.map(lambda a: draws(*a), args), True
        n = p.shape[-1]
        p = jnp.broadcast_to(p, (axis_size, n)).reshape(-1)
        seed = jnp.broadcast_to(seed, (axis_size, n)).reshape(-1)
        out = draws(codes, scale, p, seed)
        return out.reshape((axis_size, n) + out.shape[1:]), True

    return draws


@functools.partial(jax.jit, static_argnames=("bits", "block_r", "block_c",
                                             "interpret", "use_pltpu_prng"))
def flip_corrupt(codes: jax.Array, scale: jax.Array, bits: int, p, seed, *,
                 block_r: int = 256, block_c: int = 1024,
                 interpret: bool | None = None,
                 use_pltpu_prng: bool | None = None) -> jax.Array:
    """Fused flip->sign-extend->dequantize of b-bit integer codes.

    codes: (..., C) int8 with `bits` significant bits; scale: f32 scalar;
    p: flip probability (python float or traced scalar); seed: int32 scalar
    (python int or traced).  Returns f32 of codes.shape.  Under ``vmap``
    over p and/or seed, every batch member is one draw of a single kernel
    call.
    """
    if interpret is None:
        interpret = common.interpret()
    if use_pltpu_prng is None:
        use_pltpu_prng = not interpret
    shape = codes.shape
    c2 = codes.reshape((-1, shape[-1])) if codes.ndim > 1 else \
        codes.reshape((1, -1))
    r, c = c2.shape
    block_r = min(block_r, common.round_up(r, 32))
    block_c = min(block_c, common.round_up(c, 128))
    cp = common.pad_axis(common.pad_axis(c2, 0, block_r), 1, block_c)
    p_arr = jnp.asarray(p, jnp.float32).reshape((1,))
    scale_arr = jnp.asarray(scale, jnp.float32).reshape((1,))
    seed_arr = jnp.asarray(seed, jnp.int32).reshape((1,))
    out = _draws(bits, c, block_r, block_c, bool(interpret),
                 bool(use_pltpu_prng))(cp, scale_arr, p_arr, seed_arr)
    return out[0, :r, :c].reshape(shape)
