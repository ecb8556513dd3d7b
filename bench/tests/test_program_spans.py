"""The service's own profiler spans (``serve.*``) in a trace leave the
reduction unchanged: ``Trace.spans`` keeps only the benchmark's
``bench.*`` spans, so every accepted metric reads what it read before."""

import jax
import numpy as np

from bench import tracefile as tf


def test_reducer_keeps_only_bench_spans(tmp_path):
    from jax.profiler import ProfileData, TraceAnnotation

    from repro.api import make_classifier
    from repro.serving import ClassifierService

    x = jax.random.normal(jax.random.PRNGKey(0), (40, 8))
    y = jax.numpy.arange(40) % 4
    clf = make_classifier("conventional", n_classes=4, in_features=8,
                          dim=128).fit(x, y)
    svc = ClassifierService({"m": clf.model}, max_batch=4, buckets=(1, 2, 4))
    svc.warmup()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench.window"):
            svc.serve_forever()
            with TraceAnnotation("bench.submit"):
                futs = [svc.submit("m", np.asarray(x[i])) for i in range(8)]
            with TraceAnnotation("bench.result"):
                got = [f.result(timeout=30.0) for f in futs]
            svc.shutdown()
    finally:
        jax.profiler.stop_trace()
    assert got == [int(v) for v in clf.predict(x[:8])]
    path = tf.find_xplane(str(tmp_path))
    raw = {ev.name for plane in ProfileData.from_file(path).planes
           for ln in plane.lines for ev in ln.events}
    assert {"serve.admit", "serve.step", "serve.predict"} <= raw
    t = tf.read(path)
    assert {n for n, _, _ in t.spans} == {"bench.submit", "bench.result"}
