"""Plain reference of LogHD inference, independent of the program.

Encode (the repository's "cos" random-projection encoder), activations
against the normalized bundles (paper Eq. 5) and the nearest-profile decode
(Eq. 7), written out in ``jax.numpy``. It imports nothing from ``repro``.

Arrays are float32. Each of the three matmuls runs at the precision the
configuration states for it (``precision``: projection, similarity,
decode, the stages ``STAGES`` names), "default" (one bfloat16 pass on the
TPU's matrix unit, float32 accumulation) or "highest" (float32).

With ``dtype=jnp.bfloat16`` the same function computes every array and
every intermediate in bfloat16: a witness one step below the stated
float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = {"default": jax.lax.Precision.DEFAULT,
              "highest": jax.lax.Precision.HIGHEST}
STAGES = ("projection", "similarity", "decode")


def stated(config: dict, stages: tuple) -> tuple:
    """The configuration's matmul precisions, in the order of ``stages``:
    the ``STAGES`` its configuration module exports."""
    return tuple(config["precision"][s] for s in stages)


def _l2n(v, eps=1e-12):
    return v / (jnp.linalg.norm(v, axis=-1, keepdims=True) + eps)


def loghd_scores(params: dict, x: jax.Array, precision: tuple,
                 dtype=jnp.float32) -> jax.Array:
    """Class scores -||A(x) - P_c||^2, (B, F) raw rows -> (B, C).

    Larger is better; the label is the argmax."""
    if dtype == jnp.float32:
        proj_p, sim_p, dec_p = (PRECISIONS[p] for p in precision)
    else:
        proj_p = sim_p = dec_p = None

    def c(a):
        return jnp.asarray(a).astype(dtype)

    z = jnp.matmul(c(x), c(params["proj"]), precision=proj_p)
    h = jnp.cos(z + c(params["bias"])) * jnp.sin(z)
    h = _l2n(_l2n(h) - c(params["center"]))
    a = jnp.matmul(h, _l2n(c(params["bundles"])).T, precision=sim_p)
    p = c(params["profiles"])
    cross = jnp.matmul(a, p.T, precision=dec_p)
    return (2 * cross - jnp.sum(p * p, axis=-1)
            - jnp.sum(a * a, axis=-1, keepdims=True))
