"""Fused single-jit training engine: fit at the speed of predict.

PR 4 made evaluation device-resident (one jit per sweep); this module does
the same for fitting.  The eager trainers dispatch one ``onlinehd_epoch`` /
``refine_epoch`` per epoch from Python — on a 50-epoch refine that is 50
device round-trips of pure dispatch overhead.  Here the whole fit is ONE
compiled executable: ``lax.scan`` over epochs wrapping ``lax.scan`` over
minibatches, with permutation, zero-pad tail masking, in-graph PRNG key
splitting, and the update body inside the graph.

Exactness contract: the jnp path traces the SAME module-level bodies the
eager loops use (``hdc.conventional.onlinehd_step``,
``core.bundling.refine_epoch``), and jax's threefry is deterministic under
tracing — so ``fused_onlinehd_fit`` / ``fused_refine_bundles`` are
key-for-key BIT-IDENTICAL to the eager loops, not just statistically close
(tested in ``tests/test_fit_engine.py``).  The Pallas path
(``use_kernel=True``, dispatched behind ``kernels_qualify`` on compiled
TPU) folds each minibatch update into the ``bundle_update`` kernel — same
math, different float summation order, so parity there is allclose.

Compiled executables are cached in ``_FIT_JIT_CACHE`` keyed on the static
configuration (method, epochs, batch size, kernel/compression choice, mesh)
— jit itself buckets by operand shape, giving one executable per
(method, shape-bucket), zero retraces across repeated fits.  The cache
registers with ``api.dispatch.clear_cache`` so the process-wide
invalidation invariant holds.

Data-parallel: ``fused_*_dp`` shard the example axis over a mesh
(``launch/mesh.py``) via ``shard_map``; each shard computes its minibatch
delta locally and the deltas are all-reduced — optionally through the int8
error-feedback ``optim.grad_compress.compressed_psum`` (4x less all-reduce
traffic; the quantization residual rides the scan carry) — before the
replicated ``l2n(m + delta)`` finish.  Summing per-shard deltas IS the
big-batch update, so the uncompressed dp fit matches the single-device fit
on the same global batches to float-summation order.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.api import dispatch
from repro.core.bundling import refine_delta, refine_epoch, symbol_targets
from repro.hdc.conventional import (l2_normalize as _l2n, onlinehd_delta,
                                    onlinehd_step, pad_batches)
from repro.optim.grad_compress import compressed_psum

__all__ = ["fused_onlinehd_fit", "fused_refine_bundles",
           "fused_onlinehd_fit_dp", "fused_refine_bundles_dp",
           "clear_fit_cache"]


# One compiled executable per (method statics) x (operand shapes): the dict
# buckets the statics, jit buckets the shapes.  Same discipline as
# core.evaluate._SWEEP_JIT_CACHE — tests assert _cache_size() == 1 per entry
# after a full benchmark grid.
_FIT_JIT_CACHE: dict = {}


def _cached(key: tuple, builder: Callable[[], Callable]) -> Callable:
    fn = _FIT_JIT_CACHE.get(key)
    if fn is None:
        fn = _FIT_JIT_CACHE[key] = builder()
    return fn


@dispatch.register_cache_clearer
def clear_fit_cache() -> None:
    """Drop every cached compiled fit executable (also runs on
    ``api.dispatch.clear_cache()``)."""
    _FIT_JIT_CACHE.clear()


# ------------------------------------------------------------- kernel steps

def _onlinehd_step_kernel(protos, hh, yy, lr):
    """OnlineHD minibatch update through the bundle_update Pallas kernel.

    Folds the pull/push one-hots into one (B, C) coefficient matrix and
    hands the scatter-add + renormalize to the fused kernel."""
    sims = hh @ protos.T
    pred = jnp.argmax(sims, axis=-1)
    wrong = (pred != yy).astype(hh.dtype)
    s_true = jnp.take_along_axis(sims, yy[:, None], axis=-1)[:, 0]
    s_pred = jnp.take_along_axis(sims, pred[:, None], axis=-1)[:, 0]
    w_pull = wrong * (1.0 - s_true)
    w_push = wrong * (1.0 - s_pred)
    coeff = (w_pull[:, None] * jax.nn.one_hot(yy, protos.shape[0],
                                              dtype=hh.dtype)
             - w_push[:, None] * jax.nn.one_hot(pred, protos.shape[0],
                                                dtype=hh.dtype))
    return dispatch.fused_bundle_update(protos, coeff, hh, lr,
                                        use_kernel=True)


def _refine_step_kernel(bundles, hh, tt, lr):
    """Eq. 9 minibatch update through the bundle_update Pallas kernel."""
    coeff = tt - hh @ bundles.T                          # (B, n) error
    return dispatch.fused_bundle_update(bundles, coeff, hh, lr,
                                        use_kernel=True)


# --------------------------------------------------------- single-device --

def _build_onlinehd_fit(epochs: int, batch_size: int,
                        use_kernel: bool) -> Callable:
    step = _onlinehd_step_kernel if use_kernel else onlinehd_step

    def fit(protos, h, y, lr):
        hb, yb = pad_batches(h, y, batch_size)

        def epoch(p, _):
            def body(p, batch):
                hh, yy = batch
                return step(p, hh, yy, lr), None
            p, _ = jax.lax.scan(body, p, (hb, yb))
            return p, None

        protos, _ = jax.lax.scan(epoch, protos, None, length=epochs)
        return protos

    return jax.jit(fit)


def fused_onlinehd_fit(protos: jax.Array, h: jax.Array, y: jax.Array, *,
                       lr: float, batch_size: int, epochs: int,
                       use_kernel: Optional[bool] = None) -> jax.Array:
    """All OnlineHD refinement epochs in one compiled executable.

    Bit-identical to ``for _ in range(epochs): onlinehd_epoch(...)`` on the
    jnp path; the Pallas path (compiled TPU) is allclose.  ``lr`` stays a
    traced operand, so sweeping it never retraces."""
    if epochs <= 0:
        return protos
    if use_kernel is None:
        use_kernel = dispatch.kernels_qualify()
    fn = _cached(("onlinehd", int(epochs), int(batch_size), bool(use_kernel)),
                 lambda: _build_onlinehd_fit(int(epochs), int(batch_size),
                                             bool(use_kernel)))
    return fn(protos, h, y, jnp.float32(lr))


def _build_refine_fit(epochs: int, batch_size: int,
                      use_kernel: bool) -> Callable:
    def fit(bundles, h, targets_y, lr, key):
        keys = jax.random.split(key, epochs)

        def epoch(m, k):
            if not use_kernel:
                return refine_epoch(m, k, h, targets_y, lr, batch_size), None
            perm = jax.random.permutation(k, h.shape[0])
            hb, tb = pad_batches(h[perm], targets_y[perm], batch_size)

            def body(m, batch):
                hh, tt = batch
                return _refine_step_kernel(m, hh, tt, lr), None
            m, _ = jax.lax.scan(body, m, (hb, tb))
            return m, None

        bundles, _ = jax.lax.scan(epoch, bundles, keys)
        return bundles

    return jax.jit(fit)


def fused_refine_bundles(bundles: jax.Array, h: jax.Array, y: jax.Array,
                         codebook: jax.Array, k: int, *, epochs: int,
                         lr: float, batch_size: int = 1, seed: int = 0,
                         key: Optional[jax.Array] = None,
                         use_kernel: Optional[bool] = None) -> jax.Array:
    """All Eq. 9 refinement epochs in one compiled executable.

    Key-for-key bit-identical to ``core.bundling.refine_bundles`` on the
    jnp path (in-graph ``jax.random.split`` draws the same threefry stream
    as the eager host-side split); the Pallas path is allclose."""
    if epochs <= 0:
        return bundles
    if use_kernel is None:
        use_kernel = dispatch.kernels_qualify()
    targets_y = symbol_targets(codebook, k)[y]           # (N, n)
    bs = max(1, min(int(batch_size), h.shape[0]))
    if key is None:
        key = jax.random.PRNGKey(seed)
    fn = _cached(("refine", int(epochs), bs, bool(use_kernel)),
                 lambda: _build_refine_fit(int(epochs), bs,
                                           bool(use_kernel)))
    return fn(bundles, h, targets_y, jnp.float32(lr), key)


# ---------------------------------------------------------- data-parallel --

def _allreduce_delta(delta, err, axis: str, compress: Optional[str]):
    """Sum per-shard deltas over `axis`; int8 error-feedback optional."""
    if compress == "int8":
        mean, err = compressed_psum(delta, axis, err)
        return mean * jax.lax.axis_size(axis), err
    return jax.lax.psum(delta, axis), err


def _pad_rows_to(arrs, multiple: int):
    """Zero-pad axis 0 of each array to the next multiple (no-op rows)."""
    n = arrs[0].shape[0]
    total = -(-n // multiple) * multiple
    if total == n:
        return arrs
    return tuple(jnp.pad(a, ((0, total - n),) + ((0, 0),) * (a.ndim - 1))
                 for a in arrs)


def _build_onlinehd_dp(epochs: int, local_bs: int, compress: Optional[str],
                       mesh, axis: str) -> Callable:
    def local_fit(protos, h, y, lr):
        hb, yb = pad_batches(h, y, local_bs)

        def epoch(carry, _):
            def body(carry, batch):
                p, err = carry
                hh, yy = batch
                delta, err = _allreduce_delta(
                    onlinehd_delta(p, hh, yy, lr), err, axis, compress)
                return (_l2n(p + delta), err), None
            carry, _ = jax.lax.scan(body, carry, (hb, yb))
            return carry, None

        carry = (protos, jnp.zeros(protos.shape, jnp.float32))
        (protos, _), _ = jax.lax.scan(epoch, carry, None, length=epochs)
        return protos

    return jax.jit(jax.shard_map(
        local_fit, mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P()), out_specs=P(),
        check_vma=False))


def fused_onlinehd_fit_dp(protos: jax.Array, h: jax.Array, y: jax.Array, *,
                          lr: float, batch_size: int, epochs: int,
                          mesh=None, axis: str = "data",
                          compress: Optional[str] = "int8") -> jax.Array:
    """Data-parallel fused OnlineHD fit: examples sharded over ``axis``.

    Each global step consumes one ``batch_size`` batch split evenly across
    the shards; per-shard deltas are all-reduced (int8 error-feedback
    compressed when ``compress="int8"``, exact psum when ``None``) before
    the replicated normalize.  With ``compress=None`` this matches the
    single-device fused fit on the same global batches up to float
    summation order."""
    if epochs <= 0:
        return protos
    if mesh is None:
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh()
    n_shards = int(mesh.shape[axis])
    local_bs = max(1, int(batch_size) // n_shards)
    h, y = _pad_rows_to((h, y), n_shards * local_bs)
    fn = _cached(("onlinehd_dp", int(epochs), local_bs, compress, mesh, axis),
                 lambda: _build_onlinehd_dp(int(epochs), local_bs, compress,
                                            mesh, axis))
    return fn(protos, h, y, jnp.float32(lr))


def _build_refine_dp(epochs: int, local_bs: int, compress: Optional[str],
                     mesh, axis: str) -> Callable:
    def local_fit(bundles, h, targets_y, lr, key):
        keys = jax.random.split(key, epochs)

        def epoch(carry, k):
            m, err = carry
            # distinct per-shard shuffle, deterministic in (key, shard)
            k = jax.random.fold_in(k, jax.lax.axis_index(axis))
            perm = jax.random.permutation(k, h.shape[0])
            hb, tb = pad_batches(h[perm], targets_y[perm], local_bs)

            def body(carry, batch):
                m, err = carry
                hh, tt = batch
                delta, err = _allreduce_delta(
                    refine_delta(m, hh, tt, lr), err, axis, compress)
                return (_l2n(m + delta), err), None
            carry, _ = jax.lax.scan(body, (m, err), (hb, tb))
            return carry, None

        carry = (bundles, jnp.zeros(bundles.shape, jnp.float32))
        (bundles, _), _ = jax.lax.scan(epoch, carry, keys)
        return bundles

    return jax.jit(jax.shard_map(
        local_fit, mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(), P()),
        out_specs=P(), check_vma=False))


def fused_refine_bundles_dp(bundles: jax.Array, h: jax.Array, y: jax.Array,
                            codebook: jax.Array, k: int, *, epochs: int,
                            lr: float, batch_size: int, mesh=None,
                            axis: str = "data",
                            compress: Optional[str] = "int8",
                            seed: int = 0,
                            key: Optional[jax.Array] = None) -> jax.Array:
    """Data-parallel fused Eq. 9 refinement: examples sharded over ``axis``.

    Each shard shuffles its local rows per epoch (key folded with the shard
    index, so the stream is deterministic but differs from the serial key
    chain); per-shard deltas are all-reduced like
    ``fused_onlinehd_fit_dp``."""
    if epochs <= 0:
        return bundles
    if mesh is None:
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh()
    n_shards = int(mesh.shape[axis])
    local_bs = max(1, int(batch_size) // n_shards)
    targets_y = symbol_targets(codebook, k)[y]
    h, targets_y = _pad_rows_to((h, targets_y), n_shards * local_bs)
    if key is None:
        key = jax.random.PRNGKey(seed)
    fn = _cached(("refine_dp", int(epochs), local_bs, compress, mesh, axis),
                 lambda: _build_refine_dp(int(epochs), local_bs, compress,
                                          mesh, axis))
    return fn(bundles, h, targets_y, jnp.float32(lr), key)
