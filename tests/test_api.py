"""Tests for the typed estimator API (repro.api): registry round-trip,
pytree identity, checkpoint save/restore, and bit-for-bit stability of the
typed quantize->corrupt pipeline against the explicit per-leaf plumbing
(the contract the historical dict path pinned)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (HDClassifier, MethodSpec, available_methods,
                       get_method, load_model, make_classifier,
                       register_method, save_model)
from repro.api.models import ConventionalModel
from repro.core import evaluate as ev
from repro.core.faults import corrupt_model
from repro.core.quantize import QTensor, quantize_tree
from repro.hdc.encoders import encode_batched

C, F, D = 6, 16, 512

METHOD_KW = {
    "conventional": {},
    "sparsehd": dict(sparsity=0.5, retrain_epochs=3),
    "loghd": dict(k=2, extra_bundles=2, refine_epochs=3),
    "hybrid": dict(sparsity=0.5, k=2, extra_bundles=2, refine_epochs=3),
}


@functools.lru_cache(maxsize=1)
def _data():
    key = jax.random.PRNGKey(0)
    dirs = jax.random.normal(key, (C, F))
    y = jnp.repeat(jnp.arange(C), 30)
    x = dirs[y] * 2.0 + jax.random.normal(key, (len(y), F)) * 0.3
    return x, y


@functools.lru_cache(maxsize=8)
def _fitted(name: str) -> HDClassifier:
    x, y = _data()
    clf = make_classifier(name, n_classes=C, in_features=F, dim=D,
                          **METHOD_KW[name])
    return clf.fit(x, y)


def _h_test(clf: HDClassifier):
    x, _ = _data()
    return encode_batched(clf.model.enc, x, clf.enc_cfg.kind)


# ---------------------------------------------------------------- registry --

def test_all_four_methods_constructible_and_fit():
    assert set(available_methods()) >= {"conventional", "sparsehd",
                                        "loghd", "hybrid"}
    x, y = _data()
    for name in ("conventional", "sparsehd", "loghd", "hybrid"):
        clf = _fitted(name)
        assert isinstance(clf.model, get_method(name).model_cls)
        h = _h_test(clf)
        preds = clf.predict_encoded(h)
        assert preds.shape == y.shape
        # easy separable data: every method should essentially solve it
        assert float(jnp.mean(preds == y)) > 0.9, name
        assert clf.model_bits(4) > 0


def test_make_classifier_validation():
    with pytest.raises(KeyError):
        make_classifier("nope", n_classes=4, in_features=8)
    with pytest.raises(ValueError):
        make_classifier("loghd", n_classes=4)          # no encoder info
    with pytest.raises(ValueError):
        make_classifier("loghd", n_classes=4, in_features=8).predict_encoded(
            jnp.zeros((2, 16)))                        # unfitted


def test_register_custom_method():
    spec = MethodSpec("unit_test_method", ConventionalModel,
                      get_method("conventional").make_config,
                      get_method("conventional").fit)
    register_method(spec)
    try:
        assert "unit_test_method" in available_methods()
        x, y = _data()
        clf = make_classifier("unit_test_method", n_classes=C,
                              in_features=F, dim=D).fit(x, y)
        assert isinstance(clf.model, ConventionalModel)
    finally:
        from repro.api import registry
        registry._REGISTRY.pop("unit_test_method", None)


# ------------------------------------------------------------------ pytree --

@pytest.mark.parametrize("name", list(METHOD_KW))
def test_pytree_flatten_unflatten_identity(name):
    model = _fitted(name).model
    leaves, treedef = jax.tree_util.tree_flatten(model)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert type(rebuilt) is type(model)
    for a, b in zip(leaves, jax.tree_util.tree_flatten(rebuilt)[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # static aux survives the round trip
    for aux in model.aux_fields:
        assert getattr(rebuilt, aux) == getattr(model, aux)


def test_model_is_jit_transparent():
    clf = _fitted("loghd")
    h = _h_test(clf)
    direct = clf.model.predict_encoded(h)
    jitted = jax.jit(lambda m, hh: m.predict_encoded(hh))(clf.model, h)
    np.testing.assert_array_equal(np.asarray(direct), np.asarray(jitted))


# -------------------------------------------------------------- checkpoint --

@pytest.mark.parametrize("name", ["loghd", "hybrid"])
def test_checkpoint_roundtrip_f32(tmp_path, name):
    clf = _fitted(name)
    save_model(str(tmp_path), 0, clf.model)
    back = load_model(str(tmp_path))
    assert type(back) is type(clf.model)
    h = _h_test(clf)
    np.testing.assert_array_equal(
        np.asarray(clf.model.predict_encoded(h)),
        np.asarray(back.predict_encoded(h)))


def test_checkpoint_roundtrip_quantized(tmp_path):
    clf = _fitted("loghd")
    qm = clf.model.quantized(4)
    save_model(str(tmp_path), 3, qm)
    back = load_model(str(tmp_path))          # newest committed step
    assert isinstance(back.bundles, QTensor)
    assert back.bundles.bits == 4
    np.testing.assert_array_equal(np.asarray(qm.bundles.codes),
                                  np.asarray(back.bundles.codes))
    h = _h_test(clf)
    np.testing.assert_array_equal(
        np.asarray(qm.materialized().predict_encoded(h)),
        np.asarray(back.materialized().predict_encoded(h)))


# --------------------------------- parity with the explicit per-leaf path --

def test_quantize_corrupt_matches_explicit_per_leaf_pipeline():
    """Typed quantized->corrupted must be bit-for-bit identical to quantizing
    each declared stored leaf explicitly and running ``corrupt_model`` over
    the flattened field dict — the exact per-leaf PRNG key assignment the
    historical dict path used, pinned so flip streams stay stable across
    releases."""
    x, y = _data()
    for name in ("conventional", "sparsehd", "loghd", "hybrid"):
        typed = _fitted(name).model
        d = typed.to_dict()
        for leaf in typed.stored_leaves:
            d[leaf] = quantize_tree({leaf: d[leaf]}, 4)[leaf]
        key = jax.random.PRNGKey(7)
        q_typed = typed.quantized(4).corrupted(0.1, key)
        q_dict = corrupt_model(d, 0.1, key, scope="all")
        for leaf in typed.stored_leaves:
            np.testing.assert_array_equal(
                np.asarray(getattr(q_typed, leaf).codes),
                np.asarray(q_dict[leaf].codes), err_msg=f"{name}.{leaf}")


def test_evaluate_under_flips_key_reproducible():
    """Same key -> same masks -> identical accuracy, and p=0 equals clean."""
    x, y = _data()
    clf = _fitted("loghd")
    h = _h_test(clf)
    key = jax.random.PRNGKey(11)
    a1 = ev.evaluate_under_flips(clf.model, 4, 0.2, h, y, key, 2, "all")
    a2 = ev.evaluate_under_flips(clf.model, 4, 0.2, h, y, key, 2, "all")
    assert a1 == a2
    clean = ev.evaluate_under_flips(clf.model, 4, 0.0, h, y, key, 2, "all")
    q = clf.model.quantized(4).materialized()
    assert clean == pytest.approx(
        float(jnp.mean(q.predict_encoded(h) == y)), abs=1e-6)


def test_encoder_kind_survives_checkpoint(tmp_path):
    """A non-default encoder kind must ride the model through save/load so
    bare-model predict(x) re-encodes with the right featurization."""
    x, y = _data()
    clf = make_classifier("conventional", n_classes=C, in_features=F, dim=D,
                          encoder_kind="rp").fit(x, y)
    assert clf.model.encoder_kind == "rp"
    save_model(str(tmp_path), 0, clf.model)
    back = load_model(str(tmp_path))
    assert back.encoder_kind == "rp"
    np.testing.assert_array_equal(np.asarray(clf.model.predict(x)),
                                  np.asarray(back.predict(x)))


def test_sweep_jit_cache_reused():
    clf = _fitted("sparsehd")
    h = _h_test(clf)
    x, y = _data()
    before = len(ev._SWEEP_JIT_CACHE)
    ev.evaluate_under_flips(clf.model, 4, 0.1, h, y, jax.random.PRNGKey(0), 2)
    after_first = len(ev._SWEEP_JIT_CACHE)
    ev.evaluate_under_flips(clf.model, 4, 0.1, h, y, jax.random.PRNGKey(1), 2)
    assert len(ev._SWEEP_JIT_CACHE) == after_first  # one entry per (family,
    assert after_first > before                     # scope, bits) triple


# ------------------------------------------------------------- satellites --

def test_max_bundles_for_budget_enforces_floor():
    from repro.core.codebook import min_bundles
    from repro.core.loghd import max_bundles_for_budget
    # feasible: unchanged accounting
    n = max_bundles_for_budget(0.4, 26, 10_000, 2)
    assert n * (10_000 + 26) <= 0.4 * 26 * 10_000
    assert n >= min_bundles(26, 2)
    # infeasible budget: strict raises, non-strict clamps to the floor
    with pytest.raises(ValueError):
        max_bundles_for_budget(0.0001, 26, 10_000, 2)
    assert (max_bundles_for_budget(0.0001, 26, 10_000, 2, strict=False)
            == min_bundles(26, 2))


def test_loghd_head_scores_matches_reference():
    from repro.api.dispatch import loghd_head_scores
    from repro.kernels.loghd_head.ref import loghd_head_logits_ref
    key = jax.random.PRNGKey(0)
    h = jax.random.normal(key, (4, 32))
    m = jax.random.normal(jax.random.fold_in(key, 1), (3, 32))
    p = jax.random.normal(jax.random.fold_in(key, 2), (10, 3))
    out = loghd_head_scores(h, m, p, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(loghd_head_logits_ref(h, m, p)),
                               rtol=1e-5, atol=1e-5)
    # leading-dims form (the LM (B, S, D) path)
    out3 = loghd_head_scores(h.reshape(2, 2, 32), m, p, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out3.reshape(4, 10)),
                               np.asarray(out), rtol=1e-5, atol=1e-5)


def test_serving_loop_accepts_empty_prompt():
    """Regression: an empty prompt used to leave `logits` unbound in
    admit() (NameError).  Zero-length prompts must serve deterministically."""
    import dataclasses as dc
    from repro.configs import get_smoke_config
    from repro.models.model import init_params
    from repro.runtime.serve_loop import Request, ServeLoopConfig, run_serving
    cfg = dc.replace(get_smoke_config("qwen3-1.7b"), vocab=64, d_model=32,
                     n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64,
                     n_periods=1)
    params = init_params(jax.random.PRNGKey(0), cfg)
    reqs = [Request(uid=0, prompt=np.zeros((0,), np.int32)),
            Request(uid=1, prompt=np.arange(3) % 64)]
    out = run_serving(cfg, params, reqs,
                      ServeLoopConfig(batch_slots=2, max_new_tokens=4,
                                      max_len=32))
    assert set(out) == {0, 1}
    assert 1 <= len(out[0]) <= 4
    assert all(0 <= t < 64 for t in out[0])


def test_import_initializes_no_backend():
    """Importing the library asks JAX for no backend: whether kernels run
    compiled is decided when they are traced, so an import never takes a
    chip, and a later ``jax.devices()`` still sees the real backend."""
    import os
    import subprocess
    import sys
    code = ("import repro.api, repro.serving, repro.faults\n"
            "import repro.launch.compile_cache\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
