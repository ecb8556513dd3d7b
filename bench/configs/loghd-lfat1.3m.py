"""LogHD at LF-AmazonTitles-1.3M's label count: C = 1,305,265 classes,
n = ceil(log2 C) = 21 bundles, D = 10,000, F = 768 (assumed).

No fit: at this C one would need millions of encoded rows. The projection,
the bundles, the request pool and the profiles are made from the seed on
the device in one jitted call. Each profile coordinate is drawn from the
mean and spread that the pool's own activations have on it, so a query's
nearest profile is decided by its activations, as in a fitted model, and
near-ties among a million profiles are as common as in one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.loghd import Built, l2n, seed32
from bench.reference import STAGES  # noqa: F401
from bench.reference import loghd_scores as reference_scores  # noqa: F401


@functools.partial(jax.jit, static_argnames=(
    "in_features", "dim", "n_bundles", "n_classes", "pool", "bandwidth"))
def make(key, *, in_features: int, dim: int, n_bundles: int,
         n_classes: int, pool: int, bandwidth: float):
    kw, kb, km, kx, kp = jax.random.split(key, 5)
    proj = jax.random.normal(kw, (in_features, dim), jnp.float32) / (
        jnp.sqrt(jnp.float32(in_features)) * bandwidth)
    bias = jax.random.uniform(kb, (dim,), jnp.float32, 0.0, 2.0 * jnp.pi)
    bundles = l2n(jax.random.normal(km, (n_bundles, dim), jnp.float32))
    x = jax.random.normal(kx, (pool, in_features), jnp.float32)
    z = x @ proj
    h = l2n(jnp.cos(z + bias) * jnp.sin(z))
    center = jnp.mean(h, axis=0)
    acts = l2n(h - center) @ bundles.T
    profiles = jnp.mean(acts, axis=0) + jnp.std(acts, axis=0) \
        * jax.random.normal(kp, (n_classes, n_bundles), jnp.float32)
    return x, {"proj": proj, "bias": bias, "center": center,
               "bundles": bundles, "profiles": profiles}


def build(cfg: dict, seed: int) -> Built:
    x, params = make(jax.random.PRNGKey(seed32(seed)),
                     in_features=cfg["in_features"], dim=cfg["dim"],
                     n_bundles=cfg["n_bundles"], n_classes=cfg["n_classes"],
                     pool=cfg["pool"], bandwidth=cfg["bandwidth"])
    jax.block_until_ready(params)
    return Built(params=params, pool=np.asarray(x))
