"""Shape-bucketed jit cache over the dispatch predict surface.

Serving traffic arrives in arbitrary batch sizes; jit specializes on shape,
so feeding raw batches straight into ``api.dispatch.predict_fn`` would
compile a fresh executable for every distinct size the scheduler happens to
assemble.  ``BucketedPredict`` quantizes batch sizes onto a fixed ladder of
buckets (powers of two by default): a batch of n rows is padded up to the
smallest bucket >= n, so mixed batch sizes never retrace — the process
compiles at most one executable per (model family, bucket) and every later
batch that lands in the same bucket is a cache hit.

Padding is with zero rows; every predict path in the repo is row-wise
(similarities + per-row argmax), so padded rows cannot influence real rows,
and the wrapper slices the pad off before anyone sees it.  Correctness is
pinned by tests/test_serving.py (byte-identical vs unpadded
``predict_encoded`` for every registered family).

All live caches register with ``api.dispatch.register_cache_clearer`` so
``api.dispatch.clear_cache()`` remains the single invalidation entry point.
"""

from __future__ import annotations

import dataclasses
import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import dispatch
from repro.api.models import HDModel
from repro.core.quantize import QTensor

__all__ = ["bucket_sizes", "BucketedPredict"]


def bucket_sizes(max_batch: int) -> tuple[int, ...]:
    """The default bucket ladder: powers of two up to (and incl.) max_batch.

    >>> bucket_sizes(8)
    (1, 2, 4, 8)
    >>> bucket_sizes(12)
    (1, 2, 4, 8, 12)
    """
    if max_batch < 1:
        raise ValueError("max_batch must be >= 1")
    out = []
    b = 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return tuple(out)


# Live caches, so dispatch.clear_cache() (the single invalidation entry
# point) can reset serving-layer state without dispatch importing upward.
_LIVE_CACHES: "weakref.WeakSet[BucketedPredict]" = weakref.WeakSet()


@dispatch.register_cache_clearer
def _clear_all_bucket_caches() -> None:
    for cache in list(_LIVE_CACHES):
        cache.clear()


@dataclasses.dataclass
class BucketStats:
    """Per-(family, bucket) executable accounting."""
    hits: int = 0
    misses: int = 0          # first use of a (family key, bucket) pair
    padded_rows: int = 0     # pad rows dispatched, added by pad_to_bucket
                             # or by predict (wasted work proxy)

    @property
    def calls(self) -> int:
        return self.hits + self.misses


class BucketedPredict:
    """Pad-to-bucket batch assembly over ``dispatch.predict_fn``.

    ``predict(model, h)`` pads ``h`` (n, D) up to the smallest bucket >= n,
    runs the family's cached jit executable at that fixed shape, and returns
    the first n labels.  Batches larger than the top bucket are served in
    top-bucket-sized chunks, so one oversized burst cannot mint a new
    executable either.

    ``stats`` counts hits/misses per (family key, bucket): a miss is the
    first time a pair is seen (one compile), every later call is a hit —
    the "mixed batch sizes never retrace" contract the serving tests pin.
    """

    def __init__(self, buckets=None, max_batch: int = 64):
        self.buckets = (tuple(sorted(set(int(b) for b in buckets)))
                        if buckets is not None else bucket_sizes(max_batch))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"invalid bucket ladder: {self.buckets!r}")
        self.stats = BucketStats()
        self._seen: dict = {}           # (family key, bucket) -> call count
        _LIVE_CACHES.add(self)

    # ------------------------------------------------------------- shapes --
    @property
    def max_bucket(self) -> int:
        return self.buckets[-1]

    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= n (top bucket for oversized n; callers chunk).

        >>> BucketedPredict(buckets=(1, 2, 4, 8)).bucket_for(3)
        4
        """
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def pad_to_bucket(self, xs: np.ndarray) -> np.ndarray:
        """Host rows (n, ...) zero-padded to ``bucket_for(n)`` rows, the pad
        counted in ``stats.padded_rows``.  The service pads here, before
        encode, so the encoder also compiles once per bucket.

        >>> cache = BucketedPredict(buckets=(1, 2, 4, 8))
        >>> cache.pad_to_bucket(np.ones((3, 2), np.float32)).shape
        (4, 2)
        >>> cache.stats.padded_rows
        1
        """
        n = xs.shape[0]
        bucket = self.bucket_for(n)
        if n >= bucket:
            return xs
        self.stats.padded_rows += bucket - n
        return np.concatenate(
            [xs, np.zeros((bucket - n,) + xs.shape[1:], xs.dtype)])

    def _family_key(self, model: HDModel,
                    use_kernels: Optional[bool]) -> tuple:
        metric = getattr(model, "metric", "l2")
        if use_kernels is None:
            use_kernels = dispatch.kernels_qualify(metric)
        # residency: a quantized model (int8 QTensor codes, dequantized
        # in-graph) is a different executable than its f32 twin — jit keys
        # on the pytree structure, so the accounting must too
        residency = tuple((name, getattr(model, name).bits)
                          for name in model.stored_leaves
                          if isinstance(getattr(model, name), QTensor))
        return (type(model), metric, bool(use_kernels), residency)

    # ------------------------------------------------------------ predict --
    def _predict_bucket(self, model: HDModel, h: jax.Array, bucket: int,
                        use_kernels: Optional[bool]) -> jax.Array:
        """One fixed-shape dispatch: pad (n, D) -> (bucket, D), slice n."""
        n = h.shape[0]
        key = self._family_key(model, use_kernels) + (bucket,)
        if key in self._seen:
            self.stats.hits += 1
        else:
            self.stats.misses += 1
        self._seen[key] = self._seen.get(key, 0) + 1
        if n < bucket:
            h = jnp.pad(h, ((0, bucket - n), (0, 0)))
            self.stats.padded_rows += bucket - n
        labels = dispatch.predict_fn(model, use_kernels)(model, h)
        return labels[:n]

    def predict(self, model: HDModel, h: jax.Array,
                use_kernels: Optional[bool] = None) -> jax.Array:
        """Labels for (n, D) pre-encoded queries via the bucketed cache.

        Row i of the result is byte-identical to
        ``dispatch.predict_encoded(model, h)[i]`` — padded rows never leak.
        Dispatch is non-blocking (the returned labels are an async device
        array); force with ``np.asarray`` / ``block_until_ready``.
        """
        h = jnp.asarray(h)
        n = h.shape[0]
        if n == 0:
            return jnp.zeros((0,), jnp.int32)
        top = self.max_bucket
        if n <= top:
            return self._predict_bucket(model, h, self.bucket_for(n),
                                        use_kernels)
        pieces = [self._predict_bucket(model, h[i:i + top],
                                       self.bucket_for(min(top, n - i)),
                                       use_kernels)
                  for i in range(0, n, top)]
        return jnp.concatenate(pieces, axis=0)

    # ------------------------------------------------------------ metrics --
    def executables(self) -> int:
        """Distinct (family, bucket) executables this cache has dispatched."""
        return len(self._seen)

    def snapshot(self) -> dict:
        """JSON-able stats (serve bench records this next to latency)."""
        return {
            "buckets": list(self.buckets),
            "hits": self.stats.hits,
            "misses": self.stats.misses,
            "padded_rows": self.stats.padded_rows,
            "executables": self.executables(),
        }

    def clear(self) -> None:
        """Reset bucket bookkeeping (the compiled executables live in
        ``dispatch._predict_jit``, which ``dispatch.clear_cache`` drops in
        the same sweep)."""
        self._seen.clear()
        self.stats = BucketStats()
