"""LogHD at ISOLET's shape: F = 617 features, C = 26 classes, D = 10,000,
k = 2 and n = ceil(log2 26) + 5 = 10 bundles.

The model is made from the seed on the device in one jitted call, by the
paper's Algorithm 1 without its refinement epochs: encode the training
split, superpose class prototypes, bundle them by a code per class (Eq. 4)
and estimate each class's activation profile (Eq. 6). Refinement changes
accuracy, not a served shape, and would lengthen every run's set-up.
The request pool is fresh rows of the surrogate's test distribution.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.loghd import Built, l2n, seed32
from bench.reference import STAGES  # noqa: F401
from bench.reference import loghd_scores as reference_scores  # noqa: F401


def surrogate(cfg: dict, rng: np.random.Generator):
    """ISOLET-shaped class-conditional data (train, request pool),
    standardized with the training statistics.

    A copy of the repository's seeded surrogate generator: well separated
    class clusters with several modes each, plus a share of samples blended
    toward a second class."""
    d = cfg["data"]
    c, f = cfg["n_classes"], cfg["in_features"]
    m = d["modes_per_class"]
    class_dir = rng.standard_normal((c, f))
    class_dir /= np.linalg.norm(class_dir, axis=-1, keepdims=True)
    mode_off = rng.standard_normal((c, m, f))
    mode_off /= np.linalg.norm(mode_off, axis=-1, keepdims=True)
    means = d["sep"] * class_dir[:, None, :] + d["mode_scale"] * d["sep"] \
        * mode_off

    def split(n):
        y = rng.integers(0, c, size=n)
        mode = rng.integers(0, m, size=n)
        mu = means[y, mode]
        amb = rng.random(n) < d["ambiguous"]
        y2 = (y + rng.integers(1, c, size=n)) % c
        lam = rng.uniform(0.0, d["lam_max"], size=n)[:, None]
        mu = np.where(amb[:, None], (1 - lam) * mu + lam * means[y2, mode],
                      mu)
        x = mu + rng.standard_normal((n, f)) * (d["nu"] / np.sqrt(f))
        return x.astype(np.float32), y.astype(np.int32)

    x_tr, y_tr = split(cfg["n_train"])
    x_te, y_te = split(cfg["pool"])
    mu, sd = x_tr.mean(0, keepdims=True), x_tr.std(0, keepdims=True) + 1e-6
    return (x_tr - mu) / sd, y_tr, (x_te - mu) / sd, y_te


def unique_codes(n_classes: int, n_bundles: int,
                 rng: np.random.Generator) -> np.ndarray:
    """A distinct binary code per class, (C, n) in {0, 1}."""
    ids = rng.choice(2 ** n_bundles, size=n_classes, replace=False)
    bits = (ids[:, None] >> np.arange(n_bundles - 1, -1, -1)) & 1
    return bits.astype(np.float32)


@functools.partial(jax.jit, static_argnames=("dim", "n_classes",
                                             "bandwidth"))
def fit(key, x, y, codes, *, dim: int, n_classes: int, bandwidth: float):
    f = x.shape[1]
    kw, kb = jax.random.split(key)
    proj = jax.random.normal(kw, (f, dim), jnp.float32) / (
        jnp.sqrt(jnp.float32(f)) * bandwidth)
    bias = jax.random.uniform(kb, (dim,), jnp.float32, 0.0, 2.0 * jnp.pi)
    z = x @ proj
    h = l2n(jnp.cos(z + bias) * jnp.sin(z))
    center = jnp.mean(h, axis=0)
    h = l2n(h - center)
    protos = l2n(jax.ops.segment_sum(h, y, num_segments=n_classes))
    bundles = l2n(codes.T @ protos)                  # Eq. 4, g(s) = s
    acts = h @ bundles.T                             # Eq. 5
    counts = jax.ops.segment_sum(jnp.ones_like(y, jnp.float32), y,
                                 num_segments=n_classes)
    profiles = jax.ops.segment_sum(acts, y, num_segments=n_classes) \
        / jnp.maximum(counts, 1.0)[:, None]          # Eq. 6
    return {"proj": proj, "bias": bias, "center": center,
            "bundles": bundles, "profiles": profiles}


def build(cfg: dict, seed: int) -> Built:
    rng = np.random.default_rng([seed, 1])
    x_tr, y_tr, x_te, y_te = surrogate(cfg, rng)
    codes = unique_codes(cfg["n_classes"], cfg["n_bundles"], rng)
    params = fit(jax.random.PRNGKey(seed32(seed)), x_tr, y_tr, codes,
                 dim=cfg["dim"], n_classes=cfg["n_classes"],
                 bandwidth=cfg["bandwidth"])
    jax.block_until_ready(params)
    return Built(params=params, pool=x_te, labels=y_te)
