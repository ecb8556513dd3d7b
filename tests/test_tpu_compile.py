"""Compile-only tests: the main-path Pallas kernels at real widths, compiled
by the TPU compiler for a described (not attached) v5e chip.

Nothing runs, so these say nothing about results or times; they catch what
interpret mode cannot — Mosaic lowering errors, unaligned tiles, VMEM
overruns, batching rules the chip refuses.  The topology is described
inside a fixture (never at import): only one process at a time may load
the TPU compiler's library, and a worker that merely collects this file
must not take it.  The persistent compilation cache is off around the
compiles, since an entry written for a described chip cannot be read back
without one.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api import dispatch
from repro.api.models import LogHDModel
from repro.kernels.bundle_sim.ops import bundle_similarity
from repro.kernels.bundle_update.ops import bundle_update
from repro.kernels.flip_corrupt.ops import flip_corrupt
from repro.kernels.loghd_head.ops import loghd_head_logits
from repro.kernels.profile_decode.ops import profile_decode_scores

D = 10_000                        # the paper's hypervector width
N_ISOLET = 10                     # ceil(log2 26) + 5 extra bundles (k = 2)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # noqa: BLE001 — any failure means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_for_chip(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; returns the executable text."""
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shapes)
    return jax.jit(fn).lower(*args).compile().as_text()


def _s(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_bundle_sim_compiles(one_chip):
    hlo = _compile_for_chip(
        lambda h, m: bundle_similarity(h, m, interpret=False), one_chip,
        _s((64, D)), _s((N_ISOLET, D)))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("c,n", [(26, N_ISOLET), (1 << 20, 20)])
def test_profile_decode_compiles(one_chip, c, n):
    hlo = _compile_for_chip(
        lambda a, p: profile_decode_scores(a, p, interpret=False), one_chip,
        _s((64, n)), _s((c, n)))
    assert "tpu_custom_call" in hlo


def test_loghd_head_compiles(one_chip):
    hlo = _compile_for_chip(
        lambda h, m, p: loghd_head_logits(h, m, p, interpret=False),
        one_chip, _s((256, 2048)), _s((20, 2048)), _s((151_936, 20)))
    assert "tpu_custom_call" in hlo


def test_bundle_update_compiles(one_chip):
    hlo = _compile_for_chip(
        lambda m, c, h, lr: bundle_update(m, c, h, lr, interpret=False),
        one_chip, _s((N_ISOLET, D)), _s((64, N_ISOLET)), _s((64, D)),
        _s(()))
    assert "tpu_custom_call" in hlo


def test_flip_corrupt_compiles(one_chip):
    hlo = _compile_for_chip(
        lambda codes, scale, p, seed: flip_corrupt(
            codes, scale, 4, p, seed, interpret=False),
        one_chip, _s((26, D), jnp.int8), _s(()), _s(()), _s((), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_flip_corrupt_compiles_under_sweep_vmaps(one_chip):
    """The sweep engine's vmap(p-grid) o vmap(trial seeds) around the
    kernel: it must compile, as one kernel call over every draw."""
    def sweep_body(codes, scale, ps, seeds):
        return jax.vmap(lambda p: jax.vmap(
            lambda s: flip_corrupt(codes, scale, 4, p, s,
                                   interpret=False))(seeds))(ps)

    hlo = _compile_for_chip(sweep_body, one_chip, _s((26, D), jnp.int8),
                            _s(()), _s((4,)), _s((4,), jnp.int32))
    assert hlo.count("tpu_custom_call") == 1


def test_loghd_kernel_predict_compiles(one_chip, monkeypatch):
    """The LogHD predict executable that serving dispatches on the chip:
    bundle_sim then profile_decode, both as kernels.  The kernels ask the
    default backend (the CPU here) whether to interpret, so the test tells
    them they are on a TPU, and drops traces made before and after."""
    from repro.kernels import common
    monkeypatch.setattr(common, "interpret", lambda: False)
    jax.clear_caches()
    model = LogHDModel(
        enc={"proj": _s((617, D)), "bias": _s((D,)), "center": _s((D,))},
        bundles=_s((N_ISOLET, D)), profiles=_s((26, N_ISOLET)),
        codebook=_s((26, N_ISOLET), jnp.int32))
    predict = dispatch.predict_fn(model, use_kernels=True)
    try:
        hlo = _compile_for_chip(predict, one_chip, model, _s((64, D)))
    finally:
        jax.clear_caches()
    assert hlo.count("tpu_custom_call") >= 2
