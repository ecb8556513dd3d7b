"""Pallas TPU kernel: fused bit-flip corruption + dequantization.

One HBM pass over a QTensor's stored codes implements the whole
read-corrupted-memory-word pipeline of the fault-sweep engine:

    PRNG -> b-bit flip mask -> XOR -> sign-extend -> dequantize to f32

The jnp path (core.faults.flip_bits_int + quantize.dequantize) walks the
codes three times and materializes the intermediate mask and the
sign-extended int8 tensor in HBM; here every element is read once as int8
and written once as f32, with the mask generated in registers/VMEM.

Two in-kernel PRNGs:

  * ``use_pltpu_prng=True`` (compiled TPU default): the hardware PRNG via
    ``pltpu.prng_seed`` / ``pltpu.prng_random_bits``, seeded with (draw
    seed, linear block index) so blocks are decorrelated.
  * ``use_pltpu_prng=False`` (interpret default): a portable counter-hash
    PRNG (two rounds of a murmur-style 32-bit finalizer over the element's
    global linear index, the seed, and the bit plane).  It has no lowering
    dependency, its output is independent of the block decomposition, and
    ``ref.py`` reproduces it bit-for-bit in pure jnp — which is what the
    parity tests pin (the pltpu stream only exists on real TPUs).

Flip decision per bit plane: the top 24 bits of the random word are compared
against ``floor(p * 2^24)``, so p in [0, 1] maps exactly to flip probability
(p=0 flips nothing, p=1 flips every bit — both ends deterministic, which the
parity tests exploit).

Batching: one call corrupts the same codes under T (p, seed) draws — the
fault-sweep's (p, trial) batch is the innermost grid axis, and each program
reads its p and seed from SMEM by ``program_id``.  With t innermost, the
codes block index is unchanged across t, so each codes tile is read from
HBM once and written T times.  (Mosaic refuses the batched SMEM block that
``vmap`` of a single-draw kernel would need; ops.py maps ``vmap`` onto this
axis instead.)

Tiling: codes are int8 (min tile (32, 128)), output f32 (min tile (8, 128));
blocks are multiples of (32, 128), zero-padded by ops.py (padded elements
produce garbage that is sliced away; their hash indices may alias real ones,
which is harmless because every element's output depends only on its own
index).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def mix32(x: jax.Array) -> jax.Array:
    """32-bit murmur-style finalizer (full avalanche)."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> jnp.uint32(16))
    return x


def hash_u32(idx: jax.Array, seed: jax.Array, plane: int) -> jax.Array:
    """Counter-hash PRNG word for (element index, seed, bit plane)."""
    x = idx * jnp.uint32(0x9E3779B9)
    x = x + seed * jnp.uint32(0x85EBCA6B)
    x = x + jnp.uint32(plane) * jnp.uint32(0xC2B2AE35)
    return mix32(mix32(x))


def flip_threshold(p: jax.Array) -> jax.Array:
    """floor(clip(p) * 2^24) as uint32 — compare against the top 24 random
    bits.  Exact at both ends: 0 -> never flips, 1 -> always flips."""
    p = jnp.clip(p.astype(jnp.float32), 0.0, 1.0)
    # via int32: Mosaic has no float32 -> uint32 cast, and p * 2^24 fits
    return (p * jnp.float32(1 << 24)).astype(jnp.int32).astype(jnp.uint32)


def _kernel(seed_ref, p_ref, scale_ref, codes_ref, out_ref, *, bits: int,
            true_c: int, block_r: int, block_c: int, use_pltpu_prng: bool):
    i, j, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    thr = flip_threshold(p_ref[t])
    u = codes_ref[...].astype(jnp.int32) & ((1 << bits) - 1)
    shape = u.shape

    mask = jnp.zeros(shape, jnp.int32)
    if use_pltpu_prng:
        pltpu.prng_seed(seed_ref[t], i * pl.num_programs(1) + j)
        for b in range(bits):
            rnd = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
            flip = (rnd >> jnp.uint32(8)) < thr
            mask = mask | (flip.astype(jnp.int32) << b)
    else:
        rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + i * block_r
        cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + j * block_c
        idx = (rows.astype(jnp.uint32) * jnp.uint32(true_c)
               + cols.astype(jnp.uint32))
        seed = seed_ref[t].astype(jnp.uint32)
        for b in range(bits):
            rnd = hash_u32(idx, seed, b)
            flip = (rnd >> jnp.uint32(8)) < thr
            mask = mask | (flip.astype(jnp.int32) << b)

    x = u ^ mask
    if bits == 1:
        val = (2 * x - 1).astype(jnp.float32)
    else:
        x = jnp.where((x & (1 << (bits - 1))) != 0, x - (1 << bits), x)
        val = x.astype(jnp.float32)
    out_ref[...] = val * scale_ref[0]


def flip_corrupt_pallas(codes: jax.Array, scale: jax.Array, p: jax.Array,
                        seed: jax.Array, *, bits: int, true_c: int,
                        block_r: int, block_c: int, use_pltpu_prng: bool,
                        interpret: bool = True) -> jax.Array:
    """codes: (R, C) int8, already padded to (block_r, block_c) multiples;
    scale: (1,) f32; p: (T,) f32; seed: (T,) int32.  Returns (T, R, C)
    corrupted, dequantized f32, draw t made with (p[t], seed[t]) (ops.py
    slices the padding away)."""
    r, c = codes.shape
    n_draws = p.shape[0]
    assert r % block_r == 0 and c % block_c == 0, (codes.shape, block_r,
                                                   block_c)
    assert seed.shape == (n_draws,), (seed.shape, p.shape)
    return pl.pallas_call(
        functools.partial(_kernel, bits=bits, true_c=true_c, block_r=block_r,
                          block_c=block_c, use_pltpu_prng=use_pltpu_prng),
        grid=(r // block_r, c // block_c, n_draws),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_r, block_c), lambda i, j, t: (i, j)),
        ],
        out_specs=pl.BlockSpec((None, block_r, block_c),
                               lambda i, j, t: (t, i, j)),
        out_shape=jax.ShapeDtypeStruct((n_draws, r, c), jnp.float32),
        interpret=interpret,
    )(seed, p, scale, codes)
