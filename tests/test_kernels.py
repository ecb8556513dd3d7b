"""Per-kernel correctness sweeps: Pallas (interpret mode) vs pure-jnp oracle.

Every kernel is swept over shapes (aligned and deliberately ragged) and
dtypes, asserting allclose against its ref.py oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantize import dequantize, quantize
from repro.kernels.bundle_sim.ops import bundle_similarity
from repro.kernels.bundle_sim.ref import bundle_similarity_ref
from repro.kernels.bundle_update.ops import bundle_update
from repro.kernels.bundle_update.ref import bundle_update_ref
from repro.kernels.flip_corrupt.ops import flip_corrupt
from repro.kernels.flip_corrupt.ref import flip_corrupt_ref
from repro.kernels.profile_decode.ops import profile_decode_scores
from repro.kernels.profile_decode.ref import profile_decode_scores_ref
from repro.kernels.hdc_encode.ops import hdc_encode
from repro.kernels.hdc_encode.ref import hdc_encode_ref
from repro.kernels.loghd_head.ops import loghd_head_logits
from repro.kernels.loghd_head.ref import loghd_head_logits_ref


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-5, atol=1e-5)


BS_SHAPES = [
    (8, 256, 4),       # tiny, single tile
    (64, 1024, 6),     # multiple D tiles
    (100, 617, 10),    # ragged B and D (ISOLET-like)
    (256, 2048, 18),   # multiple B and D tiles, vocab-head-like n
    (33, 10000, 5),    # paper D=10k, ragged batch
]


@pytest.mark.parametrize("b,d,n", BS_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bundle_sim(b, d, n, dtype):
    kh, km = jax.random.split(jax.random.PRNGKey(b + d + n))
    h = _rand(kh, (b, d), dtype)
    m = _rand(km, (n, d), jnp.float32)
    m = m / jnp.linalg.norm(m, axis=-1, keepdims=True)
    got = bundle_similarity(h, m, interpret=True)
    want = bundle_similarity_ref(h, m)
    np.testing.assert_allclose(got, want, **_tol(dtype))
    assert got.shape == (b, n) and got.dtype == jnp.float32


PD_SHAPES = [
    (8, 4, 5),         # tiny
    (64, 6, 26),       # ISOLET-like
    (100, 10, 26),     # ragged batch
    (256, 18, 2048),   # one C tile as wide as C
    (17, 20, 151936),  # vocab-scale C, ragged everything
    (64, 21, 20037),   # several C tiles, the last one ragged
    (3, 21, 26),       # C inside one tile, n = 21
]


@pytest.mark.parametrize("b,n,c", PD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_profile_decode(b, n, c, dtype):
    ka, kp = jax.random.split(jax.random.PRNGKey(b + n + c))
    a = _rand(ka, (b, n), dtype)
    p = _rand(kp, (c, n), dtype)
    got = profile_decode_scores(a, p, interpret=True)
    want = profile_decode_scores_ref(a, p)
    np.testing.assert_allclose(got, want, **_tol(dtype))
    # argmax agreement (the decode semantics that matter)
    if dtype == jnp.float32:
        np.testing.assert_array_equal(jnp.argmax(got, -1), jnp.argmax(want, -1))


ENC_SHAPES = [
    (8, 10, 256),      # PAGE-like
    (64, 617, 1024),   # ISOLET-like
    (100, 75, 2000),   # ragged
    (32, 561, 4096),
]


@pytest.mark.parametrize("b,f,d", ENC_SHAPES)
@pytest.mark.parametrize("kind", ["cos", "rp", "rp_sign"])
def test_hdc_encode(b, f, d, kind):
    keys = jax.random.split(jax.random.PRNGKey(b + f + d), 4)
    x = _rand(keys[0], (b, f), jnp.float32)
    w = _rand(keys[1], (f, d), jnp.float32) / np.sqrt(f)
    bias = jax.random.uniform(keys[2], (d,), jnp.float32, 0, 2 * np.pi)
    center = _rand(keys[3], (d,), jnp.float32) * 0.01
    got = hdc_encode(x, w, bias, center, kind=kind, interpret=True)
    # oracle: kernel computes nonlin(xW) (center=0 passed inside), wrapper
    # then applies l2n(l2n(.) - center) — mirror with the ref
    raw = hdc_encode_ref(x, w, bias, jnp.zeros((d,)), kind)
    def l2n(v):
        return v / (jnp.linalg.norm(v, axis=-1, keepdims=True) + 1e-12)
    want = l2n(l2n(raw) - center)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # matches the production encoder exactly
    from repro.hdc.encoders import encode
    want2 = encode({"proj": w, "bias": bias, "center": center}, x, kind)
    np.testing.assert_allclose(got, want2, rtol=2e-4, atol=2e-5)


LH_SHAPES = [
    (8, 256, 4, 64),        # tiny
    (32, 1024, 18, 4096),   # multiple tiles everywhere
    (100, 2048, 20, 2048),  # ragged batch
    (16, 2048, 18, 151936), # qwen3-scale vocab
]


@pytest.mark.parametrize("b,d,n,v", LH_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_loghd_head(b, d, n, v, dtype):
    keys = jax.random.split(jax.random.PRNGKey(b + d + n + v), 3)
    h = _rand(keys[0], (b, d), dtype)
    m = _rand(keys[1], (n, d), dtype) / np.sqrt(d)
    p = _rand(keys[2], (v, n), dtype)
    got = loghd_head_logits(h, m, p, interpret=True)
    want = loghd_head_logits_ref(h, m, p)
    tol = dict(rtol=5e-2, atol=5e-1) if dtype == jnp.bfloat16 else dict(
        rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got, want, **tol)
    if dtype == jnp.float32:
        np.testing.assert_array_equal(jnp.argmax(got, -1), jnp.argmax(want, -1))


FC_SHAPES = [
    (8, 256),          # tiny, single tile
    (5, 10000),        # paper-scale bundles, ragged rows
    (26, 617),         # ragged both axes
    (100, 2000),       # multiple row tiles
]


@pytest.mark.parametrize("r,c", FC_SHAPES)
@pytest.mark.parametrize("bits", [1, 2, 4, 8])
@pytest.mark.parametrize("p", [0.0, 0.13, 1.0])
def test_flip_corrupt_matches_ref(r, c, bits, p):
    """Interpret-mode kernel (portable counter-hash PRNG) vs the jnp oracle:
    bit-exact at every p, including the deterministic endpoints."""
    w = jax.random.normal(jax.random.PRNGKey(r + c + bits), (r, c))
    q = quantize(w, bits)
    got = flip_corrupt(q.codes, q.scale, bits, p, 42, interpret=True)
    want = flip_corrupt_ref(q.codes, q.scale, p, 42, bits=bits)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.shape == q.codes.shape and got.dtype == jnp.float32


def test_flip_corrupt_p0_is_dequantize():
    w = jax.random.normal(jax.random.PRNGKey(0), (10, 1000))
    for bits in (1, 4):
        q = quantize(w, bits)
        out = flip_corrupt(q.codes, q.scale, bits, 0.0, 7, interpret=True)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(dequantize(q)))


def test_flip_corrupt_block_shape_invariant():
    """The hash PRNG indexes elements globally, so the output must not
    depend on the block decomposition."""
    w = jax.random.normal(jax.random.PRNGKey(1), (33, 700))
    q = quantize(w, 4)
    a = flip_corrupt(q.codes, q.scale, 4, 0.3, 9, interpret=True,
                     block_r=32, block_c=128)
    b = flip_corrupt(q.codes, q.scale, 4, 0.3, 9, interpret=True,
                     block_r=256, block_c=512)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_flip_corrupt_flip_rate():
    """Recovered bit-flip rate from the dequantized output ~ p."""
    p, bits = 0.25, 4
    w = jax.random.normal(jax.random.PRNGKey(2), (64, 4096))
    q = quantize(w, bits)
    out = flip_corrupt(q.codes, q.scale, bits, p, 123, interpret=True)
    codes_out = np.round(np.asarray(out) / float(q.scale)).astype(np.int64)
    x = ((codes_out & 0xF) ^ (np.asarray(q.codes, np.int64) & 0xF))
    rate = np.unpackbits(x.astype(np.uint8)).sum() / (q.codes.size * bits)
    assert abs(rate - p) < 0.01, rate


def test_flip_corrupt_traced_p_and_seed():
    """p and seed may be traced — the sweep engine vmaps over both."""
    w = jax.random.normal(jax.random.PRNGKey(3), (8, 256))
    q = quantize(w, 2)
    f = jax.jit(lambda p, s: flip_corrupt(q.codes, q.scale, 2, p, s,
                                          interpret=True))
    got = f(jnp.float32(0.13), jnp.int32(42))
    want = flip_corrupt_ref(q.codes, q.scale, 0.13, 42, bits=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_flip_corrupt_nested_vmap_is_draw_axis():
    """The sweep's vmap(p) o vmap(seed) folds into the kernel's draw axis:
    every (p, seed) member equals its own single-draw oracle, bit-exact."""
    w = jax.random.normal(jax.random.PRNGKey(4), (26, 700))
    q = quantize(w, 4)
    ps = jnp.asarray([0.0, 0.1, 1.0], jnp.float32)
    seeds = jnp.asarray([3, 11], jnp.int32)
    f = jax.jit(jax.vmap(lambda p: jax.vmap(
        lambda s: flip_corrupt(q.codes, q.scale, 4, p, s,
                               interpret=True))(seeds)))
    got = np.asarray(f(ps))
    assert got.shape == (3, 2, 26, 700)
    for a, p in enumerate(ps):
        for b, s in enumerate(seeds):
            want = flip_corrupt_ref(q.codes, q.scale, p, s, bits=4)
            np.testing.assert_array_equal(got[a, b], np.asarray(want))

    # a batch of different stored words (vmap over codes and scale) is one
    # kernel call per member, still bit-exact
    qs = [quantize(w * (i + 1), 4) for i in range(2)]
    codes = jnp.stack([q.codes for q in qs])
    scales = jnp.stack([q.scale for q in qs])
    got = np.asarray(jax.vmap(lambda c, sc: flip_corrupt(
        c, sc, 4, 0.1, 5, interpret=True))(codes, scales))
    for i, q in enumerate(qs):
        want = flip_corrupt_ref(q.codes, q.scale, 0.1, 5, bits=4)
        np.testing.assert_array_equal(got[i], np.asarray(want))


BU_SHAPES = [
    (5, 32, 512),      # tiny, single D tile
    (26, 100, 1000),   # ISOLET-like C, ragged B and D
    (3, 7, 130),       # everything ragged and below one tile
    (128, 64, 2048),   # multiple D tiles, full lane of bundles
    (26, 64, 10000),   # paper D=10k
]


@pytest.mark.parametrize("n,b,d", BU_SHAPES)
def test_bundle_update(n, b, d):
    km, kc, kh = jax.random.split(jax.random.PRNGKey(n + b + d), 3)
    m = _rand(km, (n, d), jnp.float32)
    m = m / jnp.linalg.norm(m, axis=-1, keepdims=True)
    c = _rand(kc, (b, n), jnp.float32)
    h = _rand(kh, (b, d), jnp.float32)
    got = bundle_update(m, c, h, 0.01, interpret=True)
    want = bundle_update_ref(m, c, h, 0.01)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert got.shape == (n, d) and got.dtype == jnp.float32
    # rows come back unit-norm (the fused normalization epilogue)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(got), axis=-1),
                               np.ones(n), rtol=1e-5)


def test_bundle_update_block_shape_invariant():
    """Different D-tile sizes produce allclose results (accumulation order
    differs across tiles, so bitwise equality is not expected)."""
    m = jax.random.normal(jax.random.PRNGKey(0), (26, 1536))
    m = m / jnp.linalg.norm(m, axis=-1, keepdims=True)
    c = jax.random.normal(jax.random.PRNGKey(1), (48, 26))
    h = jax.random.normal(jax.random.PRNGKey(2), (48, 1536))
    a = bundle_update(m, c, h, 0.05, interpret=True, block_d=256)
    b = bundle_update(m, c, h, 0.05, interpret=True, block_d=1536)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)


def test_bundle_update_traced_lr():
    """lr may be traced (folded into the coefficients, never a static)."""
    m = jax.random.normal(jax.random.PRNGKey(4), (8, 256))
    m = m / jnp.linalg.norm(m, axis=-1, keepdims=True)
    c = jax.random.normal(jax.random.PRNGKey(5), (16, 8))
    h = jax.random.normal(jax.random.PRNGKey(6), (16, 256))
    f = jax.jit(lambda lr: bundle_update(m, c, h, lr, interpret=True))
    for lr in (0.001, 0.1):
        np.testing.assert_allclose(f(jnp.float32(lr)),
                                   bundle_update_ref(m, c, h, lr),
                                   rtol=1e-5, atol=1e-5)
    assert f._cache_size() == 1
