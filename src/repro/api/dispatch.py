"""One jit-compiled predict surface for every classifier family.

``predict_fn(model)`` returns a cached, jit-compiled ``(model, h) -> labels``
callable.  The compiled graph dispatches to the Pallas kernels
(``bundle_sim``, ``profile_decode``, ``loghd_head``) when the configuration
qualifies — compiled TPU backend and the l2 decode metric the kernels
implement — and to the pure-jnp reference paths otherwise (CPU/interpret,
cos/maha metrics).  Both paths compute the same math; the kernel path is the
fused ASIC-shaped form.

The cache is keyed on (model class, metric, kernel choice): one trace per
family per shape set, shared across flip trials, p-grid points and benchmark
sweeps instead of re-tracing per call.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.api.models import (ConventionalModel, HDModel, HybridModel,
                              LogHDModel, SparseHDModel)
from repro.core.quantize import QTensor
from repro.kernels import common as kcommon
from repro.kernels.bundle_sim.ops import bundle_similarity
from repro.kernels.bundle_update.ops import bundle_update
from repro.kernels.flip_corrupt.ops import flip_corrupt
from repro.kernels.loghd_head.ops import loghd_head_logits
from repro.kernels.profile_decode.ops import profile_decode_scores

__all__ = ["kernels_qualify", "predict_fn", "predict_encoded",
           "loghd_head_scores", "fused_bundle_update", "corrupt_dequant",
           "corrupt_materialize", "register_cache_clearer", "clear_cache"]


def _l2n(v, axis=-1, eps=1e-12):
    return v / (jnp.linalg.norm(v, axis=axis, keepdims=True) + eps)


def kernels_qualify(metric: str = "l2") -> bool:
    """Pallas path: compiled TPU backend and the l2 metric the kernels fuse.

    On CPU (this container) the kernels run in interpret mode — orders of
    magnitude slower than XLA — so the reference path is the fast path.

    >>> kernels_qualify("cos")        # only the l2 kernels exist
    False
    """
    return (not kcommon.interpret()) and metric == "l2"


def _predict_kernel(model: HDModel, h: jax.Array) -> jax.Array:
    """Kernel-dispatched l2 predict (argmax over fused Pallas scores)."""
    if isinstance(model, ConventionalModel):
        return jnp.argmax(bundle_similarity(h, _l2n(model.protos)), axis=-1)
    if isinstance(model, SparseHDModel):
        h_s = _l2n(h[:, model.keep])
        return jnp.argmax(bundle_similarity(h_s, _l2n(model.protos)), axis=-1)
    if isinstance(model, LogHDModel):
        acts = bundle_similarity(h, _l2n(model.bundles))
        return jnp.argmax(profile_decode_scores(acts, model.profiles), axis=-1)
    if isinstance(model, HybridModel):
        h_s = _l2n(h[:, model.keep])
        acts = bundle_similarity(h_s, _l2n(model.bundles))
        return jnp.argmax(profile_decode_scores(acts, model.profiles), axis=-1)
    raise TypeError(f"no kernel dispatch for {type(model).__name__}")


@functools.lru_cache(maxsize=None)
def _predict_jit(cls: type, metric: str, use_kernels: bool) -> Callable:
    def run(model: HDModel, h: jax.Array) -> jax.Array:
        # quantized (int8-resident) models dequantize IN-GRAPH: device
        # memory holds the QTensor codes, the f32 view is a fused transient.
        # materialized() is the identity for f32 models, so both residencies
        # share this trace body (jit keys on the pytree structure, giving
        # one executable per residency).
        model = model.materialized()
        if use_kernels:
            return _predict_kernel(model, h)
        return model.predict_encoded(h)
    return jax.jit(run)


def predict_fn(model: HDModel,
               use_kernels: Optional[bool] = None) -> Callable:
    """Cached jit-compiled ``(model, h) -> labels`` for `model`'s family."""
    metric = getattr(model, "metric", "l2")
    if use_kernels is None:
        use_kernels = (kernels_qualify(metric)
                       and getattr(model, "kernel_dispatch", True))
    return _predict_jit(type(model), metric, bool(use_kernels))


def predict_encoded(model: HDModel, h: jax.Array,
                    use_kernels: Optional[bool] = None) -> jax.Array:
    """Batched predict on pre-encoded queries through the cached surface."""
    return predict_fn(model, use_kernels)(model, h)


def loghd_head_scores(x: jax.Array, bundles: jax.Array, profiles: jax.Array,
                      use_kernel: Optional[bool] = None) -> jax.Array:
    """LogHD LM-head logits -||x M^T - P_v||^2: (..., D) -> (..., V) f32.

    The serving/LM classifier-head path: dispatches to the fused
    ``loghd_head`` Pallas kernel on compiled TPU backends (unsharded call
    sites only — the caller gates on its mesh context) and to the jnp
    expansion otherwise."""
    if use_kernel is None:
        use_kernel = not kcommon.interpret()
    p = profiles.astype(jnp.float32)
    if use_kernel:
        lead = x.shape[:-1]
        h2 = x.reshape((-1, x.shape[-1]))
        out = loghd_head_logits(h2, bundles, p)
        return out.reshape(lead + (p.shape[0],))
    a = (x @ bundles.T).astype(jnp.float32)                    # (..., n)
    return (2.0 * a @ p.T - jnp.sum(p * p, axis=-1)
            - jnp.sum(a * a, axis=-1, keepdims=True))


def fused_bundle_update(m: jax.Array, coeff: jax.Array, h: jax.Array, lr,
                        use_kernel: Optional[bool] = None) -> jax.Array:
    """One training minibatch update l2n(m + lr * coeff^T h), dispatched.

    The fit engine's hot scatter-add of per-batch coefficients into
    bundles/prototypes: the ``bundle_update`` Pallas kernel (one HBM pass,
    fused row-norm reduction) on compiled TPU backends, the jnp einsum +
    ``l2_normalize`` expansion otherwise.  Both compute the same math;
    the two paths differ only in float summation order (allclose, not
    bitwise)."""
    if use_kernel is None:
        use_kernel = kernels_qualify()
    if use_kernel:
        return bundle_update(m, coeff, h, lr)
    delta = jnp.einsum("bn,bd->nd", coeff, h) * lr
    return _l2n(m + delta)


def corrupt_dequant(q: QTensor, p, key: jax.Array,
                    use_kernel: Optional[bool] = None) -> jax.Array:
    """Fused flip->sign-extend->dequantize of one QTensor leaf.

    Dispatches to the ``flip_corrupt`` Pallas kernel (one HBM pass,
    in-kernel PRNG) on compiled TPU backends, and to the jnp path
    (``faults.flip_bits_int`` + dequantize — threefry, key-for-key
    reproducible with the rest of the repo) otherwise.  The two paths draw
    different PRNG streams but the same flip distribution."""
    from repro.core.faults import flip_bits_int
    from repro.core.quantize import dequantize
    if use_kernel is None:
        use_kernel = kernels_qualify()
    if use_kernel:
        seed = jax.random.randint(key, (), 0, jnp.iinfo(jnp.int32).max)
        return flip_corrupt(q.codes, q.scale, q.bits, p, seed)
    return dequantize(flip_bits_int(q, p, key))


def corrupt_materialize(model: HDModel, p, key: jax.Array,
                        scope: str = "all",
                        use_kernel: Optional[bool] = None,
                        fault_model=None) -> HDModel:
    """Corrupt + materialize a typed model's stored state in one pass.

    The fault-sweep engine's per-trial body.  ``fault_model`` selects a
    ``repro.faults`` device-noise model (``p`` is then its severity);
    only kernel-eligible models — iid, whose corruption IS the fused
    PRNG->XOR->dequantize the ``flip_corrupt`` kernel implements — ride
    the Pallas path on qualifying backends.  Every other model (and every
    model off-TPU) takes the jnp path: one trace per (family, fault
    model), the severity staying a traced scalar, so a sweep never
    retraces across its grid.  With ``fault_model=None`` this is exactly
    the legacy behaviour — the fused kernel on qualifying backends,
    ``model.corrupted(p, key, scope).materialized()`` elsewhere,
    preserving the dict-path per-leaf key assignment bit for bit."""
    if use_kernel is None:
        use_kernel = kernels_qualify()
    if fault_model is not None and not fault_model.kernel_eligible:
        from repro.core.faults import fault_skip_set
        skip = fault_skip_set(scope)
        rest = {k: v for k, v in model.to_dict().items() if k != "enc"}
        rest = fault_model.corrupt(rest, p, key, skip=skip)
        rest["enc"] = model.enc
        aux = {n: getattr(model, n) for n in model.aux_fields}
        return type(model).from_dict(rest, **aux).materialized()
    if not use_kernel:
        return model.corrupted(p, key, scope).materialized()

    from repro.core.faults import fault_skip_set, flip_bits_f32
    from repro.core.quantize import dequantize
    skip = fault_skip_set(scope)
    d = {k: v for k, v in model.to_dict().items() if k != "enc"}
    keys = jax.random.split(key, max(len(d), 1))
    out = {}
    for i, (name, leaf) in enumerate(d.items()):
        if name in skip:
            # protected leaves still materialize (e.g. "hv"-scope profiles)
            out[name] = dequantize(leaf) if isinstance(leaf, QTensor) else leaf
        elif isinstance(leaf, QTensor):
            out[name] = corrupt_dequant(leaf, p, keys[i], use_kernel=True)
        elif jnp.issubdtype(leaf.dtype, jnp.floating):
            out[name] = flip_bits_f32(leaf, p, keys[i])
        else:
            out[name] = leaf
    out["enc"] = model.enc
    aux = {n: getattr(model, n) for n in model.aux_fields}
    return type(model).from_dict(out, **aux)


# Downstream layers (repro.serving's bucketed jit caches) register their
# clearers here so that clear_cache() stays the ONE invalidation entry point
# without dispatch importing upward.
_EXTRA_CACHE_CLEARERS: list = []


def register_cache_clearer(fn: Callable[[], None]) -> Callable[[], None]:
    """Register a zero-arg callback to run on every ``clear_cache()``.

    Layers that build their own compiled-executable caches on top of
    ``predict_fn`` (e.g. ``repro.serving``'s shape-bucketed caches) register
    here at import time, preserving the invariant that ``clear_cache()``
    invalidates *every* cached executable in the process."""
    if fn not in _EXTRA_CACHE_CLEARERS:
        _EXTRA_CACHE_CLEARERS.append(fn)
    return fn


def clear_cache() -> None:
    """Drop every cached compiled predict/sweep executable in the process.

    This is the single cache-invalidation entry point.  Invariant: after
    ``clear_cache()`` no layer holds a stale compiled executable — it clears
    the per-family ``_predict_jit`` cache, ``core.evaluate``'s module-wide
    predict/sweep caches, and every cache registered through
    ``register_cache_clearer`` (the serving layer's shape-bucketed jit
    caches register themselves on import)."""
    from repro.core.evaluate import clear_caches
    _predict_jit.cache_clear()
    clear_caches()
    for fn in list(_EXTRA_CACHE_CLEARERS):
        fn()
