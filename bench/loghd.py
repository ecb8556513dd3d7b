"""What both LogHD configurations share: the seed, the served model and
the arrays the reference is given.

The benchmark makes every weight itself, on the device, from the seed; the
program receives them as a ``LogHDModel`` and the reference reads the same
arrays, never anything the program made.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np


def seed32(seed: int, stream: int = 0) -> int:
    """A 31-bit PRNG key seed drawn from the run's ``--seed`` (which may
    exceed 32 bits; ``jax.random.PRNGKey`` keeps only the low 32)."""
    return int(np.random.default_rng([seed, stream]).integers(0, 2**31 - 1))


def l2n(v, axis=-1, eps=1e-12):
    return v / (jnp.linalg.norm(v, axis=axis, keepdims=True) + eps)


@dataclasses.dataclass
class Built:
    """A configuration made from a seed."""
    params: dict        # proj, bias, center, bundles, profiles (device)
    pool: np.ndarray    # (P, F) float32 raw request rows
    labels: np.ndarray | None = None   # true class of each pool row, if known

    def model(self):
        """The served model: LogHD, k = 2, l2 decode, cos encoder, float32
        residency. The codebook is not needed to serve and is not held."""
        from repro.api.models import LogHDModel
        p = self.params
        return LogHDModel(
            enc={"proj": p["proj"], "bias": p["bias"], "center": p["center"]},
            bundles=p["bundles"], profiles=p["profiles"], codebook=None,
            metric="l2", encoder_kind="cos")
