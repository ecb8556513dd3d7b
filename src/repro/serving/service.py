"""The classifier inference service: device-resident models behind a queue.

``ClassifierService`` is the serving counterpart of the eval path: a
multi-model registry (conventional and LogHD at matched memory serve side
by side, optionally with **int8 device residency** via ``quantize_bits``),
each model ``jax.device_put`` once at registration, a deficit-round-robin
request queue with grouped slot admission (``serving/queue.py``), and a
shape-bucketed jit cache (``serving/buckets.py``) so mixed batch sizes
compile at most one executable per (family, residency, bucket).

One service cycle (``step()``):

    admit up to max_batch queued requests for the round-robin head group
    stack features -> pad to the batch's bucket -> encode (phi is jit per
      bucket shape too, so the encoder never retraces either)
    bucketed predict through api.dispatch.predict_fn (quantized models
      dequantize in-graph; device memory holds the int8 codes)
    bind each request's future to its row of the async device result

Dispatch is non-blocking: ``step()`` returns as soon as the batch is
enqueued on device; futures force the transfer on ``result()``.  A cycle
that raises binds the exception into exactly the affected futures (the
service survives and keeps serving — no request is ever silently lost),
and ``serve_forever()`` runs the cycle loop on a background thread so
host batch assembly overlaps device execution.

Each cycle opens profiler spans (``jax.profiler.TraceAnnotation``; they
record only while a profiler session is active): ``serve.admit`` around
admission, and for a non-empty batch ``serve.step`` around the rest, with
the children ``serve.assemble`` (stack and pad), ``serve.put`` (host to
device), ``serve.encode`` and ``serve.predict`` (the two dispatches, which
block once the device queue is full) and ``serve.bind``.  An idle dispatch
thread waits inside ``serve.wait``.  ``stats()`` adds the counters
``queue_wait_s`` (summed push-to-admit time of admitted requests),
``stalls`` and ``stall_s`` (dispatch-loop iterations that overran by
``STALL_S`` or more, and their summed length).
"""

from __future__ import annotations

import threading
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.api.models import HDModel
from repro.hdc.encoders import encode
from repro.serving.buckets import BucketedPredict
from repro.serving.queue import PredictFuture, PredictRequest, RequestQueue

__all__ = ["ClassifierService"]

_encode_jit = jax.jit(encode, static_argnames="kind")

# A dispatch-loop iteration is a stall when it overruns by this much: a step
# by its whole length, an idle wait by its length beyond ``poll_s``.  About
# 10x a 1.3M-class cycle, and below the 0.12 s host stalls seen on a v5e host.
STALL_S = 0.05


class ClassifierService:
    """Continuous-batched predict service over the typed classifier API.

    >>> import jax, jax.numpy as jnp
    >>> from repro.api import make_classifier
    >>> x = jax.random.normal(jax.random.PRNGKey(0), (60, 8))
    >>> y = jnp.arange(60) % 3
    >>> clf = make_classifier("conventional", n_classes=3, in_features=8,
    ...                       dim=128).fit(x, y)
    >>> svc = ClassifierService({"conv": clf.model}, max_batch=16)
    >>> futs = [svc.submit("conv", x[i]) for i in range(5)]
    >>> svc.run_until_drained()
    5
    >>> [f.result() for f in futs] == [int(v) for v in clf.predict(x[:5])]
    True
    """

    def __init__(self, models: Optional[dict] = None, *,
                 max_batch: int = 64, buckets: Optional[Sequence[int]] = None,
                 max_depth: Optional[int] = None):
        self.max_batch = int(max_batch)
        self.bucket_cache = BucketedPredict(buckets=buckets,
                                            max_batch=self.max_batch)
        # max_depth bounds the queue: submit past it raises QueueFullError
        # (counted in stats()["rejected"]) instead of growing without bound
        self.queue = RequestQueue(max_depth=max_depth)
        self._models: dict[str, HDModel] = {}
        self._t0 = time.perf_counter()
        self._cycle_lock = threading.Lock()   # one cycle at a time
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._work = threading.Event()        # wakes an idle dispatch thread
        self.errors = 0                       # cycles that bound an exception
        self.stalls = 0                       # dispatch-loop iterations that
        self.stall_s = 0.0                    # overran by STALL_S, and their
                                              # summed length
        if models:
            for name, model in models.items():
                self.register(name, model)

    # ----------------------------------------------------------- registry --
    def register(self, name: str, model: HDModel, *,
                 quantize_bits: Optional[int] = None) -> None:
        """Add (or replace) a served model; moved device-resident once here,
        never per request.

        With ``quantize_bits=b`` the stored leaves are post-training
        quantized first and the device holds the int8 ``QTensor`` codes —
        for b=8 that is 0.25x the f32 bytes per replica; predict dequantizes
        in-graph through the family's ``materialized()`` plumbing, so labels
        match ``predict_encoded`` on the quantized-then-materialized model
        exactly."""
        if not isinstance(model, HDModel):
            raise TypeError(f"served models are typed repro.api models, got "
                            f"{type(model).__name__}")
        if quantize_bits is not None:
            model = model.quantized(int(quantize_bits))
        else:
            model = model.materialized()
        self._models[name] = jax.device_put(model)

    def model(self, name: str) -> HDModel:
        try:
            return self._models[name]
        except KeyError:
            raise KeyError(f"unknown served model {name!r}; registered: "
                           f"{sorted(self._models)}") from None

    def served_models(self) -> tuple[str, ...]:
        return tuple(sorted(self._models))

    def model_bytes(self, name: str) -> int:
        """Device-resident bytes of `name`'s stored leaves (int8 residency
        is ~0.25x the f32 rows; the shared encoder is not counted, matching
        ``model_bits`` accounting)."""
        return self.model(name).stored_bytes()

    # -------------------------------------------------------------- clock --
    def now(self) -> float:
        """Seconds since service start (the arrival/latency clock)."""
        return time.perf_counter() - self._t0

    # ------------------------------------------------------------- warmup --
    def warmup(self, model_names: Optional[Sequence[str]] = None) -> int:
        """Precompile every (model, bucket) executable — encode and predict.

        A service start-up step: after warmup, steady-state traffic never
        pays a compile, whatever batch sizes the scheduler assembles (the
        open-loop latency percentiles then measure serving, not tracing).
        Covers BOTH input forms: the raw-feature path (encode per bucket,
        then predict) and the encoded-input path — ``submit`` normalizes
        every input to f32, so an encoded (bucket, D) f32 submission hits
        the same predict executable the encode path compiled; the direct
        bucket-cache call here pins that.  Returns the number of
        (model, bucket) pairs touched."""
        pairs = 0
        labels = None
        for name in (model_names if model_names is not None
                     else self.served_models()):
            model = self.model(name)
            n_feat = model.enc["proj"].shape[0]
            dim = model.enc["proj"].shape[1]
            for b in self.bucket_cache.buckets:
                h = _encode_jit(model.enc,
                                jnp.zeros((b, n_feat), jnp.float32),
                                kind=model.encoder_kind)
                labels = self.bucket_cache.predict(model, h)
                # the encoded-input form: same (bucket, D) f32 aval as the
                # encode output, so this is a cache hit, not a new trace —
                # warmed explicitly so the contract cannot drift
                labels = self.bucket_cache.predict(
                    model, jnp.zeros((b, dim), jnp.float32))
                pairs += 1
        if labels is not None:
            jax.block_until_ready(labels)
        return pairs

    # ------------------------------------------------------------- submit --
    def submit(self, model_name: str, x, *, encoded: bool = False,
               t_arrival: Optional[float] = None) -> PredictFuture:
        """Enqueue one request; returns its future.

        ``x`` is one feature vector (F,) — or one pre-encoded hypervector
        (D,) with ``encoded=True``.  Inputs are validated and normalized to
        f32 here, so a malformed submit raises immediately (never poisoning
        a service cycle) and int/f64 submissions reuse the f32 executables
        ``warmup()`` compiled instead of minting hidden per-dtype ones.
        ``t_arrival`` (service-clock seconds) lets open-loop load
        generators stamp the scheduled arrival.

        With a bounded queue (``max_depth=...``) a submit past the bound
        raises ``QueueFullError`` — backpressure the caller handles —
        and is counted in ``stats()["rejected"]``."""
        model = self.model(model_name)              # fail fast on bad name
        x = np.asarray(x, np.float32)               # one dtype, one executable
        want = model.enc["proj"].shape[1 if encoded else 0]
        if x.shape != (want,):
            form = "pre-encoded hypervector" if encoded else "feature vector"
            raise ValueError(
                f"{model_name!r} expects a ({want},) {form}, got shape "
                f"{x.shape} — one request per submit; batch via repeated "
                f"submits (the scheduler batches for you)")
        req = PredictRequest(
            uid=self.queue.next_uid(), model_name=model_name,
            x=x, encoded=bool(encoded),
            t_arrival=self.now() if t_arrival is None else float(t_arrival))
        self.queue.push(req)
        self._work.set()                            # wake the dispatch thread
        return req.future

    # --------------------------------------------------------------- step --
    def step(self) -> list[PredictRequest]:
        """Run one service cycle; returns the admitted requests (empty if
        the queue was empty).  Non-blocking: results stay on device.

        Errors are bound, not raised: if any stage of the cycle throws, the
        exception lands in exactly this batch's futures (``result()``
        re-raises it) and the service keeps serving the rest of the queue.
        """
        with self._cycle_lock:
            with TraceAnnotation("serve.admit"):
                batch = self.queue.admit(self.max_batch)
            if not batch:
                return []
            with TraceAnnotation("serve.step"):
                try:
                    model = self.model(batch[0].model_name)
                    with TraceAnnotation("serve.assemble"):
                        xs = self.bucket_cache.pad_to_bucket(
                            np.stack([r.x for r in batch]))
                    with TraceAnnotation("serve.put"):
                        h = jnp.asarray(xs)
                    if not batch[0].encoded:
                        with TraceAnnotation("serve.encode"):
                            h = _encode_jit(model.enc, h,
                                            kind=model.encoder_kind)
                    with TraceAnnotation("serve.predict"):
                        labels = self.bucket_cache.predict(model, h)
                    with TraceAnnotation("serve.bind"):
                        for row, req in enumerate(batch):
                            req.future._bind(labels, row)
                except Exception as exc:     # noqa: BLE001 — bound, not lost
                    self.errors += 1
                    for req in batch:
                        req.future._set_exception(exc)
            return batch

    def run_until_drained(self, block: bool = False) -> int:
        """Cycle until the queue is empty; returns requests admitted.
        With ``block=True`` also waits for the last device result."""
        total = 0
        labels = None
        while len(self.queue):
            batch = self.step()
            total += len(batch)
            if batch:
                labels = batch[-1].future._batch
        if block and labels is not None:
            jax.block_until_ready(labels)
        return total

    # -------------------------------------------------- background thread --
    def serve_forever(self, *, poll_s: float = 0.01) -> None:
        """Start the background dispatch thread: it runs ``step()`` in a
        loop, so host batch assembly for cycle k+1 overlaps the device
        executing cycle k (dispatch is async) and callers just ``submit``
        and ``result(timeout=...)``.  Idempotent-unsafe: raises if already
        serving.  ``poll_s`` caps the idle re-check interval (submits wake
        the thread immediately)."""
        if self._thread is not None and self._thread.is_alive():
            raise RuntimeError("serve_forever() already running — "
                               "shutdown() first")
        self._stop.clear()

        def _loop():
            while not self._stop.is_set():
                t0 = time.perf_counter()
                if self.step():
                    length = over = time.perf_counter() - t0
                else:
                    with TraceAnnotation("serve.wait"):
                        self._work.wait(poll_s)
                    self._work.clear()
                    length = time.perf_counter() - t0
                    over = length - poll_s
                if over >= STALL_S:
                    with self._cycle_lock:
                        self.stalls += 1
                        self.stall_s += length

        self._thread = threading.Thread(
            target=_loop, name="classifier-service-dispatch", daemon=True)
        self._thread.start()

    def serving(self) -> bool:
        """True while the background dispatch thread is running."""
        return self._thread is not None and self._thread.is_alive()

    def shutdown(self, *, drain: bool = True,
                 timeout: Optional[float] = None) -> None:
        """Stop the background dispatch thread (no-op if not serving).

        With ``drain=True`` (default) any still-queued requests are served
        synchronously after the thread stops, so shutdown never strands a
        pending future; with ``drain=False`` they stay queued (a later
        ``step()``/``serve_forever()`` picks them up)."""
        if self._thread is not None:
            self._stop.set()
            self._work.set()                 # unblock an idle wait
            self._thread.join(timeout)
            self._thread = None
        if drain:
            self.run_until_drained()

    # -------------------------------------------------------------- stats --
    def stats(self) -> dict:
        return {
            "served_models": list(self.served_models()),
            "admitted": self.queue.admitted,
            "cycles": self.queue.cycles,
            "queued": len(self.queue),
            "rejected": self.queue.rejected,
            "max_depth": self.queue.max_depth,
            "errors": self.errors,
            "queue_wait_s": self.queue.queue_wait_s,
            "stalls": self.stalls,
            "stall_s": self.stall_s,
            "max_group_wait_cycles": self.queue.max_group_wait_cycles,
            "serving": self.serving(),
            "bucket_cache": self.bucket_cache.snapshot(),
        }
