"""Shared helpers for the Pallas TPU kernels.

All kernels follow the same conventions:
  * explicit BlockSpec grids with VMEM-resident blocks,
  * f32 accumulation scratch regardless of input dtype,
  * hardware-aligned tile sizes (multiples of (8, 128) for f32, (16, 128)
    for bf16; the MXU prefers 128x128 operand tiles),
  * inputs are zero-padded by the ops.py wrappers to tile multiples (zeros
    are exact identities for dot products and sums of squares), and outputs
    sliced back — so the kernels see no ragged blocks, except
    profile_decode: it reads the profile table unpadded, and the lanes of
    its last, ragged C tile past C are computed but never stored,
  * interpret mode off the TPU and compiled mode on it, decided by
    ``interpret()`` when a kernel is traced.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def interpret() -> bool:
    """True unless JAX's default backend is a TPU: the kernels then run in
    Pallas interpret mode.  Asked when a kernel is traced, never while a
    module is imported, so importing ``repro`` initializes no backend."""
    return jax.default_backend() != "tpu"


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def pad_axis(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    """Zero-pad `axis` of x up to the next multiple."""
    size = x.shape[axis]
    target = round_up(size, multiple)
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads)


def sublane(dtype) -> int:
    """Minimum second-to-last-dim tile for a dtype on TPU."""
    if dtype == jnp.bfloat16:
        return 16
    if dtype in (jnp.int8, jnp.uint8):
        return 32
    return 8
