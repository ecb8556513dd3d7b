"""End-to-end behaviour tests for the paper's system: the full LogHD
pipeline (encode -> prototypes -> codebook -> bundles -> profiles ->
refine -> decode) against the paper's own claims, on a small surrogate,
driven entirely through the typed estimator API."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import make_classifier
from repro.core.evaluate import accuracy, evaluate_under_flips
from repro.core.loghd import memory_bits
from repro.data.synth import load_dataset
from repro.hdc.conventional import class_prototypes, predict_from_encoded
from repro.hdc.encoders import EncoderConfig, encode_batched, fit_encoder


@pytest.fixture(scope="module")
def isolet_small():
    x_tr, y_tr, x_te, y_te, spec = load_dataset("isolet", max_train=1500,
                                                max_test=500)
    enc_cfg = EncoderConfig(spec.n_features, 4096, "cos")
    enc, h_tr = fit_encoder(enc_cfg, jnp.asarray(x_tr))
    h_te = encode_batched(enc, jnp.asarray(x_te), "cos")
    protos = class_prototypes(h_tr, jnp.asarray(y_tr), spec.n_classes)
    return dict(spec=spec, enc_cfg=enc_cfg, enc=enc, x_tr=jnp.asarray(x_tr),
                y_tr=jnp.asarray(y_tr), h_tr=h_tr, h_te=h_te,
                y_te=np.asarray(y_te), protos=protos)


def _fit_loghd_clf(fx, **kw):
    clf = make_classifier("loghd", fx["spec"].n_classes,
                          enc_cfg=fx["enc_cfg"], **kw)
    return clf.fit(fx["x_tr"], fx["y_tr"], prototypes=fx["protos"],
                   enc=fx["enc"], encoded=fx["h_tr"])


def test_conventional_accuracy_in_paper_regime(isolet_small):
    fx = isolet_small
    acc = float(jnp.mean(predict_from_encoded(fx["protos"], fx["h_te"])
                         == fx["y_te"]))
    assert acc > 0.85, acc


def test_loghd_competitive_at_log_memory(isolet_small):
    """C1: LogHD within ~10 points of conventional at <45% of the memory."""
    fx = isolet_small
    c, d = fx["spec"].n_classes, 4096
    conv = float(jnp.mean(predict_from_encoded(fx["protos"], fx["h_te"])
                          == fx["y_te"]))
    clf = _fit_loghd_clf(fx, k=2, extra_bundles=5, refine_epochs=30,
                         codebook_method="distance")
    acc = accuracy(clf.model, fx["h_te"], fx["y_te"])
    assert acc > conv - 0.10, (acc, conv)
    assert memory_bits(c, d, clf.cfg.n_bundles, 32) < 0.45 * c * d * 32


def test_bundle_flip_robustness_mechanism(isolet_small):
    """The D-preservation mechanism: 1-bit bundles under p=0.2 flips (bulk
    scope) keep >=80% of clean accuracy."""
    fx = isolet_small
    clf = _fit_loghd_clf(fx, k=2, extra_bundles=5, refine_epochs=30,
                         codebook_method="distance")
    key = jax.random.PRNGKey(0)
    clean = evaluate_under_flips(clf.model, 1, 0.0, fx["h_te"], fx["y_te"],
                                 key, 1, "hv")
    noisy = evaluate_under_flips(clf.model, 1, 0.2, fx["h_te"], fx["y_te"],
                                 key, 2, "hv")
    assert noisy >= 0.8 * clean, (clean, noisy)


def test_distance_codebook_improves_all_scope_robustness(isolet_small):
    """Beyond-paper claim: max-min-distance codebooks don't lose to the
    load-only greedy under full-scope flips at matched everything."""
    fx = isolet_small
    key = jax.random.PRNGKey(1)
    accs = {}
    for method in ("greedy", "distance"):
        clf = _fit_loghd_clf(fx, k=2, extra_bundles=5, refine_epochs=30,
                             codebook_method=method)
        accs[method] = evaluate_under_flips(clf.model, 1, 0.1, fx["h_te"],
                                            fx["y_te"], key, 3, "all")
    assert accs["distance"] >= accs["greedy"] - 0.02, accs


def test_sparsehd_baseline_works(isolet_small):
    fx = isolet_small
    clf = make_classifier("sparsehd", fx["spec"].n_classes,
                          enc_cfg=fx["enc_cfg"], sparsity=0.6,
                          retrain_epochs=15)
    clf = clf.fit(fx["x_tr"], fx["y_tr"], prototypes=fx["protos"],
                  enc=fx["enc"], encoded=fx["h_tr"])
    acc = accuracy(clf.model, fx["h_te"], fx["y_te"])
    assert acc > 0.8
    assert clf.model.protos.shape[1] == int(0.4 * 4096)


@pytest.mark.parametrize("args", [[], ["--four-chips"]])
def test_chip_smoke_fails_without_a_tpu(args):
    """The chip smoke run never falls back to the CPU: with no TPU it exits
    non-zero and prints no result line."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "chip_smoke.py"), *args],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr
