"""Serving subsystem tests: fair (deficit-round-robin) admission and the
bounded-wait no-starvation guarantee, the full future lifecycle (pending ->
dispatched -> done/failed/cancelled, timeouts), error propagation (a failing
cycle binds its exception into exactly the affected futures — zero lost
requests), submit validation + dtype normalization (no hidden per-dtype
executables), the background dispatch thread, quantized (int8) device
residency, bucket selection and padding correctness, jit-cache hit
accounting across mixed batch sizes (the no-retrace-per-request contract),
byte-identical predictions vs the direct dispatch path for every registered
family, the single cache-invalidation entry point, and the service's
profiler spans and counters (queue wait, padded rows, stalls)."""

import functools
import pathlib
import threading
import time
from concurrent.futures import CancelledError

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import dispatch, make_classifier, predict_encoded
from repro.hdc.encoders import encode_batched
from repro.serving import (BucketedPredict, ClassifierService, PredictFuture,
                           PredictRequest, QueueFullError, RequestQueue,
                           bucket_sizes, closed_loop, open_loop_poisson)
from repro.serving.service import _encode_jit

C, F, D = 5, 12, 256

METHOD_KW = {
    "conventional": {},
    "sparsehd": dict(sparsity=0.5, retrain_epochs=2),
    "loghd": dict(k=2, extra_bundles=1, refine_epochs=2),
    "hybrid": dict(sparsity=0.5, k=2, extra_bundles=1, refine_epochs=2),
}


@functools.lru_cache(maxsize=1)
def _data():
    key = jax.random.PRNGKey(0)
    dirs = jax.random.normal(key, (C, F))
    y = jnp.arange(90) % C
    x = dirs[y] * 2.0 + jax.random.normal(key, (len(y), F)) * 0.3
    return x, y


@functools.lru_cache(maxsize=8)
def _fitted(name: str):
    x, y = _data()
    return make_classifier(name, n_classes=C, in_features=F, dim=D,
                           **METHOD_KW[name]).fit(x, y)


# ------------------------------------------------------------------ queue --

def _req(q, name, x=None, encoded=False):
    return PredictRequest(uid=q.next_uid(), model_name=name,
                          x=np.zeros(3) if x is None else x, encoded=encoded)


def test_admission_fifo_grouped_by_model():
    q = RequestQueue()
    for name in ["a", "b", "a", "b", "a"]:
        q.push(_req(q, name))
    first = q.admit(max_batch=8)
    assert [r.model_name for r in first] == ["a", "a", "a"]
    assert [r.uid for r in first] == [0, 2, 4]          # arrival order kept
    second = q.admit(max_batch=8)
    assert [r.uid for r in second] == [1, 3]            # b's kept their order
    assert q.admit(max_batch=8) == []
    assert q.admitted == 5 and q.cycles == 2


def test_admission_respects_max_batch():
    q = RequestQueue()
    for _ in range(7):
        q.push(_req(q, "m"))
    assert [r.uid for r in q.admit(max_batch=4)] == [0, 1, 2, 3]
    assert [r.uid for r in q.admit(max_batch=4)] == [4, 5, 6]


def test_admission_groups_on_input_form():
    # raw-feature and pre-encoded requests never share a cycle (different
    # input widths cannot stack into one batch)
    q = RequestQueue()
    q.push(_req(q, "m", x=np.zeros(3), encoded=False))
    q.push(_req(q, "m", x=np.zeros(9), encoded=True))
    q.push(_req(q, "m", x=np.zeros(3), encoded=False))
    assert [r.uid for r in q.admit(8)] == [0, 2]
    assert [r.uid for r in q.admit(8)] == [1]


def test_future_requires_dispatch():
    fut = PredictFuture()
    assert not fut.done()
    with pytest.raises(RuntimeError):
        fut.result()


# ----------------------------------------------------- fairness (no HoL) --

def test_no_cross_model_starvation_under_hot_load():
    """The adversarial arrival pattern the strict head-group FIFO lost to:
    a hot model floods the queue faster than one cycle drains it, a cold
    model's request arrives after the backlog.  DRR must admit the cold
    head within n_groups cycles."""
    q = RequestQueue()
    for _ in range(50):
        q.push(_req(q, "hot"))
    cold = q.push(_req(q, "cold"))
    served_cold_at = None
    for cycle in range(6):
        batch = q.admit(max_batch=8)
        for _ in range(8):                  # sustain the flood between cycles
            q.push(_req(q, "hot"))
        if any(r.model_name == "cold" for r in batch):
            served_cold_at = cycle
            break
    assert served_cold_at is not None, "cold model starved"
    assert served_cold_at < 2               # n_groups == 2 bounds the wait
    assert q.max_group_wait_cycles < 2
    assert not cold.dispatched()            # queue-level test: no service


def test_round_robin_cycles_all_groups():
    q = RequestQueue()
    for name in ["a"] * 5 + ["b"] * 5 + ["c"] * 5:
        q.push(_req(q, name))
    order = []
    while len(q):
        batch = q.admit(max_batch=2)
        order.append(batch[0].model_name)
        assert len({r.group for r in batch}) == 1   # grouped-slot contract
    assert order == ["a", "b", "c"] * 3      # 5 reqs / 2 slots -> 3 rounds
    assert q.max_group_wait_cycles <= 3


def test_service_fairness_bounded_wait_under_saturation():
    conv, log = _fitted("conventional"), _fitted("loghd")
    x, _ = _data()
    svc = ClassifierService({"hot": conv.model, "cold": log.model},
                            max_batch=4, buckets=(1, 2, 4))
    for i in range(24):
        svc.submit("hot", np.asarray(x[i % len(x)]))
    cold_fut = svc.submit("cold", np.asarray(x[0]))
    svc.step()                              # serves one hot batch
    svc.step()                              # DRR: cold is next, not hot
    assert cold_fut.dispatched()
    svc.run_until_drained()
    assert cold_fut.result() == int(log.predict(x[:1])[0])
    assert svc.stats()["max_group_wait_cycles"] <= 2


# ------------------------------------------------------- future lifecycle --

def test_future_timeout_and_cancel():
    fut = PredictFuture()
    with pytest.raises(TimeoutError):
        fut.result(timeout=0.01)
    with pytest.raises(TimeoutError):
        fut.exception(timeout=0.01)
    assert fut.cancel() and fut.cancelled() and fut.done()
    assert fut.cancel()                     # idempotent
    with pytest.raises(CancelledError):
        fut.result()
    with pytest.raises(CancelledError):
        fut.exception()
    # cancel() loses once dispatched
    fut2 = PredictFuture()
    fut2._bind(np.asarray([7]), 0)
    assert not fut2.cancel() and not fut2.cancelled()
    assert fut2.result(timeout=1.0) == 7 and fut2.exception() is None


def test_done_reflects_readiness_not_dispatch():
    """done() must not claim readiness while the device result is still in
    flight; dispatched() keeps the old meaning."""
    class FakeBatch:
        ready = False

        def is_ready(self):
            return self.ready

        def __array__(self, dtype=None):
            return np.asarray([3], dtype)

    fut = PredictFuture()
    batch = FakeBatch()
    fut._bind(batch, 0)
    assert fut.dispatched() and not fut.done()   # in flight
    batch.ready = True
    assert fut.done()
    assert fut.result() == 3 and fut.done()


def test_cancelled_request_never_dispatches():
    clf = _fitted("conventional")
    x, _ = _data()
    svc = ClassifierService({"m": clf.model}, max_batch=8)
    futs = [svc.submit("m", np.asarray(x[i])) for i in range(3)]
    assert futs[1].cancel()
    assert svc.run_until_drained() == 2      # the cancelled slot was skipped
    assert futs[0].result() == int(clf.predict(x[:1])[0])
    with pytest.raises(CancelledError):
        futs[1].result()
    assert futs[2].result() == int(clf.predict(x[:3])[2])


# ------------------------------------------------------ error propagation --

def test_cycle_error_binds_into_exactly_affected_futures():
    """A malformed request that slips past submit (here: injected straight
    into the queue) fails its cycle — the exception lands in exactly that
    cycle's futures, every other request still resolves, and the service
    keeps serving."""
    clf = _fitted("conventional")
    x, _ = _data()
    svc = ClassifierService({"m": clf.model}, max_batch=4)
    first = [svc.submit("m", np.asarray(x[i])) for i in range(4)]
    poisoned = [svc.submit("m", np.asarray(x[4]))]
    bad = PredictRequest(uid=svc.queue.next_uid(), model_name="m",
                         x=np.zeros(5, np.float32))   # wrong feature width
    svc.queue.push(bad)
    poisoned.append(bad.future)
    poisoned += [svc.submit("m", np.asarray(x[i])) for i in (5, 6)]
    last = [svc.submit("m", np.asarray(x[i])) for i in range(7, 11)]
    svc.run_until_drained()

    want = [int(v) for v in clf.predict(x[:11])]
    assert [f.result() for f in first] == want[:4]          # clean cycle
    for f in poisoned:                # the failed cycle's 4 slots — exactly
        assert isinstance(f.exception(), ValueError)
        with pytest.raises(ValueError):
            f.result()
    assert [f.result() for f in last] == want[7:11]          # service alive
    assert svc.errors == 1 and len(svc.queue) == 0           # zero lost


def test_submit_validates_shape():
    clf = _fitted("conventional")
    svc = ClassifierService({"m": clf.model}, max_batch=4)
    with pytest.raises(ValueError, match="feature vector"):
        svc.submit("m", np.zeros(F + 1))
    with pytest.raises(ValueError, match="hypervector"):
        svc.submit("m", np.zeros(F), encoded=True)      # F != D
    with pytest.raises(ValueError):
        svc.submit("m", np.zeros((2, F)))               # batch via submits
    assert len(svc.queue) == 0                          # nothing poisoned


def test_submit_normalizes_dtype_no_hidden_executables():
    """int/f64 submissions (raw AND encoded) must reuse the f32 executables
    warmup compiled — zero post-warmup compiles for both input forms."""
    clf = _fitted("conventional")
    x, _ = _data()
    h = encode_batched(clf.model.enc, x, "cos")
    svc = ClassifierService({"m": clf.model}, max_batch=4, buckets=(1, 2, 4))
    svc.warmup()
    misses = svc.bucket_cache.stats.misses
    enc_traces = _encode_jit._cache_size()
    jfn = dispatch.predict_fn(clf.model)
    predict_traces = jfn._cache_size()

    futs = [svc.submit("m", np.asarray(x[i], np.float64)) for i in range(3)]
    futs += [svc.submit("m", np.asarray(h[i], np.float64), encoded=True)
             for i in range(3)]
    futs += [svc.submit("m", np.asarray(x[3]).astype(np.int32) * 0 + 1)]
    svc.run_until_drained()
    [f.result() for f in futs]

    assert svc.bucket_cache.stats.misses == misses
    assert _encode_jit._cache_size() == enc_traces
    assert jfn._cache_size() == predict_traces
    want = [int(v) for v in clf.predict(x[:3])]
    assert [f.result() for f in futs[:3]] == want


# ------------------------------------------------------ background thread --

def test_serve_forever_background_dispatch():
    clf = _fitted("conventional")
    x, _ = _data()
    svc = ClassifierService({"m": clf.model}, max_batch=8, buckets=(1, 2, 4, 8))
    svc.warmup()
    svc.serve_forever()
    try:
        assert svc.serving()
        with pytest.raises(RuntimeError):
            svc.serve_forever()             # already running
        futs = [svc.submit("m", np.asarray(x[i])) for i in range(20)]
        got = [f.result(timeout=30.0) for f in futs]
    finally:
        svc.shutdown()
    assert not svc.serving()
    assert got == [int(v) for v in clf.predict(x[:20])]


def test_shutdown_drains_pending():
    clf = _fitted("conventional")
    x, _ = _data()
    svc = ClassifierService({"m": clf.model}, max_batch=4)
    futs = [svc.submit("m", np.asarray(x[i])) for i in range(6)]
    svc.shutdown()                          # not serving: still drains
    assert [f.result() for f in futs] == [int(v) for v in clf.predict(x[:6])]


# ---------------------------------------------------- quantized residency --

def test_quantized_residency_serves_quantized_labels():
    """register(quantize_bits=8) holds int8 codes on device (<= 0.5x the
    f32 stored bytes) and serves labels identical to predict_encoded on the
    quantized-then-materialized model."""
    clf = _fitted("loghd")
    x, _ = _data()
    h = encode_batched(clf.model.enc, x, "cos")
    svc = ClassifierService(max_batch=8, buckets=(1, 2, 4, 8))
    svc.register("f32", clf.model)
    svc.register("int8", clf.model, quantize_bits=8)
    assert svc.model_bytes("int8") <= 0.5 * svc.model_bytes("f32")

    futs = [svc.submit("int8", np.asarray(h[i]), encoded=True)
            for i in range(11)]
    svc.run_until_drained()
    got = np.asarray([f.result() for f in futs])
    want = predict_encoded(clf.model.quantized(8).materialized(), h[:11])
    np.testing.assert_array_equal(got, np.asarray(want))


def test_quantized_and_f32_residency_are_distinct_executables():
    clf = _fitted("conventional")
    x, _ = _data()
    svc = ClassifierService(max_batch=4, buckets=(2, 4))
    svc.register("f32", clf.model)
    svc.register("int8", clf.model, quantize_bits=8)
    assert svc.warmup() == 4                 # 2 models x 2 buckets
    assert svc.bucket_cache.executables() == 4   # residency extends the key
    misses = svc.bucket_cache.stats.misses
    for name in ("f32", "int8"):             # steady state: all cache hits
        futs = [svc.submit(name, np.asarray(x[i])) for i in range(3)]
        svc.run_until_drained()
        [f.result() for f in futs]
    assert svc.bucket_cache.stats.misses == misses


# ---------------------------------------------------------------- buckets --

def test_bucket_ladder_and_selection():
    assert bucket_sizes(8) == (1, 2, 4, 8)
    assert bucket_sizes(12) == (1, 2, 4, 8, 12)
    cache = BucketedPredict(buckets=(1, 2, 4, 8))
    assert [cache.bucket_for(n) for n in (1, 2, 3, 5, 8, 100)] \
        == [1, 2, 4, 8, 8, 8]
    with pytest.raises(ValueError):
        bucket_sizes(0)


def test_padding_never_leaks_into_outputs():
    clf = _fitted("loghd")
    x, _ = _data()
    h = encode_batched(clf.model.enc, x, "cos")
    cache = BucketedPredict(buckets=(4, 16, 64))
    direct = np.asarray(predict_encoded(clf.model, h))
    for n in (1, 3, 4, 5, 17, 64):
        got = np.asarray(cache.predict(clf.model, h[:n]))
        assert got.shape == (n,)
        np.testing.assert_array_equal(got, direct[:n], err_msg=f"n={n}")


def test_oversized_batches_chunk_through_the_top_bucket():
    clf = _fitted("conventional")
    x, _ = _data()
    h = encode_batched(clf.model.enc, x, "cos")       # 90 rows > top bucket
    cache = BucketedPredict(buckets=(8, 32))
    got = np.asarray(cache.predict(clf.model, h))
    np.testing.assert_array_equal(got, np.asarray(predict_encoded(
        clf.model, h)))
    # 90 = 32 + 32 + 26 -> buckets 32, 32, 32: one executable only
    assert cache.executables() == 1


def test_mixed_batch_sizes_compile_one_executable_per_bucket():
    clf = _fitted("conventional")
    x, _ = _data()
    h = encode_batched(clf.model.enc, x, "cos")
    cache = BucketedPredict(buckets=(1, 2, 4, 8))
    jfn = dispatch.predict_fn(clf.model)
    base_shapes = jfn._cache_size()
    sizes = [1, 3, 5, 7, 2, 8, 3, 5, 1, 6, 4, 7]      # mixed, repeating
    for n in sizes:
        cache.predict(clf.model, h[:n])
    used_buckets = {cache.bucket_for(n) for n in sizes}
    assert cache.executables() == len(used_buckets)
    assert cache.stats.misses == len(used_buckets)
    assert cache.stats.hits == len(sizes) - len(used_buckets)
    # the underlying jit compiled exactly one trace per bucket shape —
    # mixed batch sizes never retrace
    assert jfn._cache_size() - base_shapes <= len(used_buckets)


def test_clear_cache_resets_bucket_caches():
    clf = _fitted("conventional")
    x, _ = _data()
    h = encode_batched(clf.model.enc, x, "cos")
    cache = BucketedPredict(buckets=(4,))
    cache.predict(clf.model, h[:2])
    assert cache.executables() == 1
    dispatch.clear_cache()          # the single invalidation entry point
    assert cache.executables() == 0
    assert cache.stats.misses == 0 and cache.stats.hits == 0


# ---------------------------------------------------------------- service --

@pytest.mark.parametrize("name", list(METHOD_KW))
def test_service_byte_identical_to_predict_encoded(name):
    clf = _fitted(name)
    x, _ = _data()
    h = encode_batched(clf.model.enc, x, "cos")
    svc = ClassifierService({name: clf.model}, max_batch=8,
                            buckets=(1, 2, 4, 8))
    futs = [svc.submit(name, np.asarray(h[i]), encoded=True)
            for i in range(11)]
    svc.run_until_drained()
    got = np.asarray([f.result() for f in futs])
    np.testing.assert_array_equal(
        got, np.asarray(predict_encoded(clf.model, h[:11])),
        err_msg=f"{name}: served labels diverge from dispatch path")


def test_service_raw_features_match_full_pipeline():
    clf = _fitted("loghd")
    x, _ = _data()
    svc = ClassifierService({"loghd": clf.model}, max_batch=16)
    futs = [svc.submit("loghd", np.asarray(x[i])) for i in range(9)]
    assert svc.run_until_drained() == 9
    got = [f.result() for f in futs]
    assert got == [int(v) for v in clf.predict(x[:9])]


def test_service_multi_model_side_by_side():
    conv, log = _fitted("conventional"), _fitted("loghd")
    x, _ = _data()
    svc = ClassifierService({"conv": conv.model, "loghd": log.model},
                            max_batch=8)
    futs = {}
    for i in range(10):
        name = "conv" if i % 2 else "loghd"
        futs[i] = (name, svc.submit(name, np.asarray(x[i])))
    svc.run_until_drained()
    conv_labels = [int(v) for v in conv.predict(x[:10])]
    log_labels = [int(v) for v in log.predict(x[:10])]
    for i, (name, fut) in futs.items():
        want = conv_labels[i] if name == "conv" else log_labels[i]
        assert fut.result() == want, (i, name)


def test_warmup_precompiles_every_bucket():
    clf = _fitted("conventional")
    x, _ = _data()
    svc = ClassifierService({"m": clf.model}, max_batch=8,
                            buckets=(1, 2, 4, 8))
    assert svc.warmup() == 4
    assert svc.bucket_cache.executables() == 4
    misses = svc.bucket_cache.stats.misses
    for n in (1, 3, 8, 5):              # every bucket already compiled:
        futs = [svc.submit("m", np.asarray(x[i])) for i in range(n)]
        svc.run_until_drained()
        [f.result() for f in futs]
    assert svc.bucket_cache.stats.misses == misses
    assert svc.bucket_cache.executables() == 4


def test_service_validation():
    svc = ClassifierService(max_batch=4)
    with pytest.raises(KeyError):
        svc.submit("nope", np.zeros(3))
    with pytest.raises(TypeError):
        svc.register("bad", {"protos": np.zeros((2, 3))})


def test_bounded_queue_backpressure():
    """A queue with ``max_depth`` rejects the (max_depth+1)-th push with
    ``QueueFullError``, counts it, and accepts again once a cycle drains
    slots; unbounded queues never reject."""
    q = RequestQueue(max_depth=3)
    futs = [q.push(_req(q, "m")) for _ in range(3)]
    with pytest.raises(QueueFullError):
        q.push(_req(q, "m"))
    with pytest.raises(QueueFullError):
        q.push(_req(q, "other"))             # depth is global, not per group
    assert q.rejected == 2 and len(q) == 3
    assert q.admit(2) and len(q) == 1        # drained two slots
    q.push(_req(q, "m"))                     # accepted again
    assert len(q) == 2 and q.rejected == 2
    for f in futs:
        assert not f.cancelled()             # accepted futures untouched
    with pytest.raises(ValueError):
        RequestQueue(max_depth=0)


def test_service_backpressure_counted_in_stats():
    clf = _fitted("conventional")
    x, _ = _data()
    svc = ClassifierService({"m": clf.model}, max_batch=4, max_depth=2)
    svc.submit("m", x[0]); svc.submit("m", x[1])
    with pytest.raises(QueueFullError):
        svc.submit("m", x[2])
    st = svc.stats()
    assert st["rejected"] == 1 and st["max_depth"] == 2 and st["queued"] == 2
    svc.run_until_drained()
    fut = svc.submit("m", x[2])              # space again after the drain
    svc.run_until_drained()
    assert fut.result() == int(clf.predict(x[2:3])[0])


# ---------------------------------------------------------------- loadgen --

def test_closed_loop_stats_sane():
    clf = _fitted("conventional")
    x, _ = _data()
    svc = ClassifierService({"m": clf.model}, max_batch=16)
    res = closed_loop(svc, "m", np.asarray(x[:40]))
    assert res.n_requests == 40
    assert res.rps > 0 and res.wall_s > 0
    assert res.p50_ms <= res.p99_ms <= res.max_ms + 1e-9


def test_open_loop_poisson_completes_all_requests():
    clf = _fitted("conventional")
    x, _ = _data()
    svc = ClassifierService({"m": clf.model}, max_batch=16)
    res = open_loop_poisson(svc, "m", np.asarray(x[:16]), rate_rps=2000.0,
                            n_requests=25, seed=1)
    assert res.n_requests == 25
    assert res.n_rejected == 0               # unbounded queue: no shedding
    assert res.p50_ms <= res.p99_ms
    assert len(svc.queue) == 0


def test_open_loop_counts_rejections_under_bounded_queue():
    """Open-loop + bounded queue: arrivals that find the queue full are shed
    (counted in ``n_rejected``), every accepted request still completes, and
    accepted + rejected accounts for every scheduled arrival."""
    clf = _fitted("conventional")
    x, _ = _data()
    svc = ClassifierService({"m": clf.model}, max_batch=1, max_depth=1)
    n = 30
    res = open_loop_poisson(svc, "m", np.asarray(x[:8]), rate_rps=50_000.0,
                            n_requests=n, seed=3)
    assert res.n_requests + res.n_rejected == n
    assert res.n_rejected > 0                # this rate must overrun depth 1
    assert res.n_rejected == svc.stats()["rejected"]
    assert len(svc.queue) == 0
    assert "n_rejected" in res.to_record()


# -------------------------------------------------- spans and counters --

STEP_CHILDREN = ["serve.assemble", "serve.bind", "serve.encode",
                 "serve.predict", "serve.put"]


def _serve_lines(log_dir):
    """serve.* events of each host line (thread) of the recorded trace."""
    from jax.profiler import ProfileData
    path, = pathlib.Path(log_dir).rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    lines = [[(ev.name, ev.start_ns, ev.end_ns) for ev in ln.events
              if ev.name.startswith("serve.")]
             for plane in data.planes if plane.name.startswith("/host:")
             for ln in plane.lines]
    return [ln for ln in lines if ln]


def test_serve_forever_traces_one_span_tree_per_cycle(tmp_path):
    """Under the profiler every non-empty cycle is one serve.step holding
    its five stages, all on the dispatch thread's line; admission and
    the idle wait are spans of that line too."""
    clf = _fitted("conventional")
    x, _ = _data()
    svc = ClassifierService({"m": clf.model}, max_batch=4, buckets=(1, 2, 4))
    svc.warmup()
    jax.profiler.start_trace(str(tmp_path))
    try:
        svc.serve_forever()
        time.sleep(0.05)                    # idle first: serve.wait spans
        futs = [svc.submit("m", np.asarray(x[i])) for i in range(10)]
        got = [f.result(timeout=30.0) for f in futs]
        svc.shutdown()
    finally:
        jax.profiler.stop_trace()
    assert got == [int(v) for v in clf.predict(x[:10])]
    line, = _serve_lines(tmp_path)
    steps = [(s, e) for n, s, e in line if n == "serve.step"]
    assert len(steps) == svc.stats()["cycles"] >= 3
    for s, e in steps:
        inside = sorted(n for n, cs, ce in line
                        if s <= cs and ce <= e and n != "serve.step")
        assert inside == STEP_CHILDREN
    names = {n for n, _, _ in line}
    assert names == {"serve.admit", "serve.step", "serve.wait",
                     *STEP_CHILDREN}


def test_queue_wait_grows_with_a_held_queue():
    clf = _fitted("conventional")
    x, _ = _data()
    svc = ClassifierService({"m": clf.model}, max_batch=8)
    for i in range(3):
        svc.submit("m", np.asarray(x[i]))
    time.sleep(0.05)
    assert svc.stats()["queue_wait_s"] == 0.0      # nothing admitted yet
    svc.step()
    held = svc.stats()["queue_wait_s"]
    assert held >= 3 * 0.05
    svc.submit("m", np.asarray(x[3]))
    time.sleep(0.02)
    svc.step()
    assert svc.stats()["queue_wait_s"] >= held + 0.02


def test_service_pad_counts_in_padded_rows():
    """The service pads before encode; the bucket cache counts that pad."""
    clf = _fitted("conventional")
    x, _ = _data()
    svc = ClassifierService({"m": clf.model}, max_batch=8,
                            buckets=(1, 2, 4, 8))
    futs = [svc.submit("m", np.asarray(x[i])) for i in range(3)]
    assert svc.run_until_drained() == 3              # one batch in bucket 4
    assert svc.stats()["bucket_cache"]["padded_rows"] == 1
    assert [f.result() for f in futs] == [int(v) for v in clf.predict(x[:3])]


class _LateWake(threading.Event):
    """An idle wait that overruns its timeout once, by ``late`` seconds."""

    def __init__(self, late):
        super().__init__()
        self.late = late

    def wait(self, timeout=None):
        if self.late:
            time.sleep(timeout + self.late)
            self.late = 0
            return False
        return super().wait(timeout)


@pytest.mark.parametrize("slow", ["step", "wait"])
def test_dispatch_loop_counts_an_overrun_as_one_stall(slow, monkeypatch):
    """A step 60 ms long, or an idle wait 60 ms past ``poll_s``, is one
    stall; ``stall_s`` holds its whole length."""
    clf = _fitted("conventional")
    x, _ = _data()
    svc = ClassifierService({"m": clf.model}, max_batch=4, buckets=(1, 2, 4))
    svc.warmup()
    late = 0.06
    if slow == "step":
        real = svc.bucket_cache.predict

        def slow_predict(*args, **kw):
            time.sleep(late)
            return real(*args, **kw)

        monkeypatch.setattr(svc.bucket_cache, "predict", slow_predict)
    else:
        svc._work = _LateWake(late)
    svc.serve_forever(poll_s=1.0)    # a normal wait ends well inside poll_s
    try:
        if slow == "step":
            assert svc.submit("m", np.asarray(x[0])).result(timeout=30.0) \
                == int(clf.predict(x[:1])[0])
        deadline = time.perf_counter() + 30.0
        while svc.stats()["stalls"] == 0 and time.perf_counter() < deadline:
            time.sleep(0.01)
    finally:
        svc.shutdown()
    st = svc.stats()
    assert st["stalls"] == 1
    assert st["stall_s"] >= late + (1.0 if slow == "wait" else 0.0)
