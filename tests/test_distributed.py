"""Distributed semantics tests, run in subprocesses with 8 host devices
(the main pytest process must keep seeing 1 device).

Covers: MoE shard_map EP == single-device reference; sharded train step;
sequence-sharded flash-decode == plain decode; int8 gradient compression;
class-sharded LogHD fit/predict bitwise parity, registry/checkpoint wiring,
jit-cache discipline, and the extreme-C smoke."""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _run(body: str, timeout=600):
    script = ("import os\n"
              "os.environ['XLA_FLAGS'] = "
              "'--xla_force_host_platform_device_count=8'\n"
              f"import sys; sys.path.insert(0, {SRC!r})\n"
              "from repro.launch.mesh import make_mesh\n" + body)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0 and "OK" in out.stdout, \
        (out.stdout[-1000:], out.stderr[-3000:])


def test_moe_shard_map_matches_reference():
    _run(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models.moe import MoEConfig, init_moe, moe_block
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = MoEConfig(d_model=32, d_ff=16, n_experts=8, top_k=2,
                        capacity_factor=8.0)  # high cf: no drops -> exact
        params = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
        y_ref, aux_ref = moe_block(params, cfg, x, None)
        y_sh, aux_sh = jax.jit(
            lambda p, x: moe_block(p, cfg, x, mesh))(params, x)
        np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-4)
        # aux is PER-SHARD load balance averaged (mean of products), which
        # intentionally differs from the global product — same order only
        assert 0.1 * float(aux_ref) < float(aux_sh) < 10 * float(aux_ref)
        print("OK")
    """))


def test_sharded_train_step_runs_and_matches():
    _run(textwrap.dedent("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.models.model import init_params, loss_fn
        from repro.models.sharding import tree_shardings, batch_spec
        from jax.sharding import NamedSharding
        cfg = dataclasses.replace(get_smoke_config("qwen3-1.7b"),
                                  vocab=128, n_periods=1)
        mesh = make_mesh((2, 4), ("data", "model"))
        params = init_params(jax.random.PRNGKey(0), cfg)
        tok = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 128)
        tgt = jnp.roll(tok, -1, 1)
        ref = float(loss_fn(params, cfg, tok, tgt, None))
        shardings = tree_shardings(params, mesh)
        p_sh = jax.device_put(params, shardings)
        bs = NamedSharding(mesh, batch_spec(mesh))
        got = float(jax.jit(
            lambda p, a, b: loss_fn(p, cfg, a, b, mesh),
            in_shardings=(shardings, bs, bs))(p_sh,
                jax.device_put(tok, bs), jax.device_put(tgt, bs)))
        np.testing.assert_allclose(got, ref, rtol=2e-3)
        print("OK")
    """))


def test_seq_sharded_flash_decode_matches():
    _run(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np, functools
        from jax.sharding import PartitionSpec as P
        from repro.models.attention import (AttnConfig, init_attn,
                                            decode_attention,
                                            decode_attention_seqsharded,
                                            init_kv_cache)
        mesh = make_mesh((8,), ("data",))
        cfg = AttnConfig(d_model=32, n_heads=4, n_kv_heads=2, head_dim=8)
        params = init_attn(jax.random.PRNGKey(0), cfg, jnp.float32)
        S = 64
        cache = init_kv_cache(cfg, batch=2, max_len=S, dtype=jnp.float32)
        # warm the cache with random history
        k = jax.random.normal(jax.random.PRNGKey(1), cache["k"].shape)
        v = jax.random.normal(jax.random.PRNGKey(2), cache["v"].shape)
        cache = {"k": k, "v": v}
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 1, 32))
        pos = jnp.asarray(40, jnp.int32)
        ref, _ = decode_attention(params, cfg, x, cache, pos)

        def body(p, x, c):
            out, newc = decode_attention_seqsharded(p, cfg, x, c, pos,
                                                    axis="data")
            return out, newc
        got, _ = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), {"k": P(None, "data"), "v": P(None, "data")}),
            out_specs=(P(), {"k": P(None, "data"), "v": P(None, "data")}),
            check_vma=False))(params, x, cache)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        print("OK")
    """))


def test_grad_compression_error_feedback():
    _run(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim.grad_compress import compressed_psum
        mesh = make_mesh((8,), ("pod",))
        g_global = jax.random.normal(jax.random.PRNGKey(0), (8, 64, 32))

        def body(g, err):
            mean, new_err = compressed_psum(g[0], "pod", err[0])
            return mean[None], new_err[None]
        err0 = jnp.zeros((8, 64, 32))
        mean, err = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P("pod"), P("pod")),
            out_specs=(P("pod"), P("pod")), check_vma=False))(g_global, err0)
        want = jnp.mean(g_global, axis=0)
        # int8 quantized mean within a couple scale steps of the true mean
        scale = jnp.max(jnp.abs(g_global)) / 127.0
        np.testing.assert_allclose(np.asarray(mean[0]), np.asarray(want),
                                   atol=float(scale) * 3)
        # error feedback captured the residual
        assert float(jnp.mean(jnp.abs(err))) > 0
        print("OK")
    """))


def test_multipod_mesh_builds():
    _run(textwrap.dedent("""
        import jax
        # 8 host devices: shrink the production mesh factors but keep the
        # 3-axis (pod, data, model) structure
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        assert mesh.shape == {"pod": 2, "data": 2, "model": 2}
        print("OK")
    """))


def test_fused_fit_dp_matches_serial():
    """fused_onlinehd_fit_dp(compress=None): summing per-shard minibatch
    deltas IS the big-batch update, so the dp fit equals the single-device
    fused fit run on the interleaved global batch order."""
    _run(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.api import fit_engine
        from repro.hdc.conventional import class_prototypes, l2_normalize
        mesh = make_mesh((8,), ("data",))
        n, d, c, bs = 512, 128, 7, 64
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        h = l2_normalize(jax.random.normal(ks[0], (n, d)))
        y = jax.random.randint(ks[1], (n,), 0, c)
        protos = class_prototypes(h, y, c)

        dp = fit_engine.fused_onlinehd_fit_dp(
            protos, h, y, lr=3e-3, batch_size=bs, epochs=3,
            mesh=mesh, compress=None)

        # serial equivalent: shard s holds rows [s*64, (s+1)*64); global
        # batch b interleaves local batch b of every shard
        local_bs = bs // 8
        order = np.concatenate([
            np.concatenate([np.arange(local_bs) + b * local_bs + s * 64
                            for s in range(8)])
            for b in range(64 // local_bs)])
        serial = fit_engine.fused_onlinehd_fit(
            protos, h[order], y[order], lr=3e-3, batch_size=bs, epochs=3,
            use_kernel=False)
        np.testing.assert_allclose(np.asarray(dp), np.asarray(serial),
                                   rtol=1e-5, atol=1e-6)

        # int8 error-feedback compression stays close to the exact fit
        dp8 = fit_engine.fused_onlinehd_fit_dp(
            protos, h, y, lr=3e-3, batch_size=bs, epochs=3,
            mesh=mesh, compress="int8")
        np.testing.assert_allclose(np.asarray(dp8), np.asarray(dp),
                                   rtol=1e-3, atol=1e-3)

        # ragged row count pads to whole shard batches and still runs
        ragged = fit_engine.fused_onlinehd_fit_dp(
            protos, h[:500], y[:500], lr=3e-3, batch_size=bs, epochs=1,
            mesh=mesh, compress=None)
        assert ragged.shape == protos.shape
        print("OK")
    """))


def test_fused_refine_dp_reduces_target_error():
    """fused_refine_bundles_dp: per-shard shuffles differ from the serial
    key chain, so assert the training effect (Eq. 9 target error drops)
    rather than bitwise equality."""
    _run(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.api import fit_engine
        from repro.core.bundling import symbol_targets
        from repro.core.codebook import build_codebook
        from repro.hdc.conventional import l2_normalize
        mesh = make_mesh((8,), ("data",))
        n, d, c = 512, 128, 7
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        h = l2_normalize(jax.random.normal(ks[0], (n, d)))
        y = jax.random.randint(ks[1], (n,), 0, c)
        book = jnp.asarray(build_codebook(c, 3, 2, seed=0))
        m0 = l2_normalize(jax.random.normal(ks[2], (3, d)))

        def err(m):
            ty = symbol_targets(book, 2)[y]
            return float(jnp.mean((h @ m.T - ty) ** 2))

        m = fit_engine.fused_refine_bundles_dp(
            m0, h, y, book, 2, epochs=10, lr=1e-2, batch_size=64,
            mesh=mesh, compress="int8")
        assert m.shape == m0.shape
        assert err(m) < err(m0), (err(m), err(m0))
        # deterministic in the key
        m2 = fit_engine.fused_refine_bundles_dp(
            m0, h, y, book, 2, epochs=10, lr=1e-2, batch_size=64,
            mesh=mesh, compress="int8")
        np.testing.assert_array_equal(np.asarray(m), np.asarray(m2))
        print("OK")
    """))


def test_sharded_loghd_bitwise_parity():
    """Class-sharded LogHD fit AND predict are bitwise identical to the
    single-device path — across 1/2/8-way shardings, an uneven C % n_shards
    remainder (C=13), an even split (C=16), and both decode metrics."""
    _run(textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.api._impl import fit_loghd_model
        from repro.api.sharded import fit_loghd_sharded, shard_loghd_model
        from repro.core.loghd import LogHDConfig
        from repro.hdc.encoders import EncoderConfig, fit_encoder
        rng = np.random.default_rng(0)
        F, N, D = 24, 260, 128
        for C, metric in ((13, "l2"), (16, "l2"), (13, "cos")):
            x = jnp.asarray(rng.normal(size=(N, F)).astype(np.float32))
            y = jnp.asarray(rng.integers(0, C, size=N).astype(np.int32))
            enc_cfg = EncoderConfig(F, D, "cos")
            enc, h = fit_encoder(enc_cfg, x)
            base = LogHDConfig(n_classes=C, refine_epochs=3, metric=metric)
            ref = fit_loghd_model(base, enc_cfg, x, y, enc=enc, encoded=h)
            ht = jnp.asarray(rng.normal(size=(37, D)).astype(np.float32))
            pref = np.asarray(ref.predict_encoded(ht))
            for S in (1, 2, 8):
                import dataclasses
                cfg = dataclasses.replace(base, class_sharding=S)
                sh = fit_loghd_sharded(cfg, enc_cfg, x, y, enc=enc,
                                       encoded=h)
                np.testing.assert_array_equal(np.asarray(ref.bundles),
                                              np.asarray(sh.bundles))
                np.testing.assert_array_equal(np.asarray(ref.profiles),
                                              np.asarray(sh.profiles)[:C])
                np.testing.assert_array_equal(
                    pref, np.asarray(sh.predict_encoded(ht)))
                # re-laying a fitted single-device model is also bitwise
                rs = shard_loghd_model(ref, S)
                np.testing.assert_array_equal(
                    pref, np.asarray(rs.predict_encoded(ht)))
        print("OK")
    """))


def test_sharded_loghd_registry_and_checkpoint():
    """make_classifier("loghd", ..., class_sharding=8) routes to the
    sharded estimator; save_model/load_model round-trips the layout; the
    jit predict surface and the gathered export agree bitwise."""
    _run(textwrap.dedent("""
        import tempfile, numpy as np, jax, jax.numpy as jnp
        from repro.api import (dispatch, load_model, make_classifier,
                               save_model, ShardedLogHDModel)
        rng = np.random.default_rng(1)
        C, F, N, D = 13, 24, 260, 128
        x = jnp.asarray(rng.normal(size=(N, F)).astype(np.float32))
        y = jnp.asarray(rng.integers(0, C, size=N).astype(np.int32))
        clf = make_classifier("loghd", n_classes=C, in_features=F, dim=D,
                              refine_epochs=3, class_sharding=8).fit(x, y)
        assert isinstance(clf.model, ShardedLogHDModel)
        assert clf.model.class_sharding == 8
        assert clf.model.n_classes == C
        xt = jnp.asarray(rng.normal(size=(29, F)).astype(np.float32))
        p = np.asarray(clf.predict(xt))

        d = tempfile.mkdtemp()
        save_model(d, 0, clf.model)
        m2 = load_model(d)
        assert isinstance(m2, ShardedLogHDModel)
        assert (m2.class_sharding, m2.n_classes_real) == (8, C)
        np.testing.assert_array_equal(p, np.asarray(
            clf.with_model(m2).predict(xt)))

        # jit surface and plain gathered export agree with the eager path
        ht = jnp.asarray(rng.normal(size=(29, D)).astype(np.float32))
        pe = np.asarray(clf.model.predict_encoded(ht))
        np.testing.assert_array_equal(
            pe, np.asarray(dispatch.predict_encoded(clf.model, ht)))
        np.testing.assert_array_equal(
            pe, np.asarray(clf.model.gathered().predict_encoded(ht)))
        # accounting uses the REAL C, not the padded row count
        assert clf.model.model_bits(8) == clf.model.gathered().model_bits(8)
        print("OK")
    """))


def test_sharded_loghd_cache_discipline():
    """One executable per (shard layout, batch bucket) on the jit predict
    surface: a batch ladder compiles once per shape, re-running it (and
    re-fitting) compiles nothing new; the fit caches stay put too."""
    _run(textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.api import dispatch, make_classifier
        from repro.api import fit_engine, sharded
        rng = np.random.default_rng(2)
        C, F, N, D = 16, 24, 260, 128
        x = jnp.asarray(rng.normal(size=(N, F)).astype(np.float32))
        y = jnp.asarray(rng.integers(0, C, size=N).astype(np.int32))

        def fit(S):
            return make_classifier("loghd", n_classes=C, in_features=F,
                                   dim=D, refine_epochs=2,
                                   class_sharding=S).fit(x, y)

        ladder = [1, 8, 64]
        models = {S: fit(S).model for S in (2, 4)}
        jfn = dispatch.predict_fn(models[2])
        assert jfn is dispatch.predict_fn(models[4])  # one surface, same key
        before = jfn._cache_size()
        for S, m in models.items():
            for b in ladder:
                ht = jnp.asarray(rng.normal(size=(b, D)).astype(np.float32))
                jfn(m, ht).block_until_ready()
        grew = jfn._cache_size() - before
        assert grew == len(models) * len(ladder), grew

        fit_caches = (len(fit_engine._FIT_JIT_CACHE),
                      len(sharded._SHARDED_JIT_CACHE))
        # repeat the whole ladder and refit both layouts: ZERO new traces
        models2 = {S: fit(S).model for S in (2, 4)}
        for S, m in models2.items():
            for b in ladder:
                ht = jnp.asarray(rng.normal(size=(b, D)).astype(np.float32))
                jfn(m, ht).block_until_ready()
        assert jfn._cache_size() - before == grew
        assert (len(fit_engine._FIT_JIT_CACHE),
                len(sharded._SHARDED_JIT_CACHE)) == fit_caches
        print("OK")
    """))


def test_sharded_loghd_extreme_smoke():
    """C = 2^16 over 8 class shards: fits without any C x D array, memory
    splits ~1/n_shards (<= 1.2x ideal), predictions stay in range (the
    2^20 point runs in benchmarks/extreme_bench.py)."""
    _run(textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.api import make_classifier, ShardedLogHDModel
        rng = np.random.default_rng(3)
        C, F, N, D = 1 << 16, 32, 2048, 256
        x = jnp.asarray(rng.normal(size=(N, F)).astype(np.float32))
        y = jnp.asarray(rng.integers(0, C, size=N).astype(np.int32))
        clf = make_classifier("loghd", n_classes=C, in_features=F, dim=D,
                              refine_epochs=1, class_sharding=8).fit(x, y)
        m = clf.model
        assert isinstance(m, ShardedLogHDModel)
        info = m.resident_bytes_per_device()
        assert info["ratio_to_ideal"] <= 1.2, info
        # every device holds a real (not replicated) slice of the rows
        assert info["max_bytes_per_device"] * 8 <= info["total_bytes"] * 1.01
        ht = jnp.asarray(rng.normal(size=(64, D)).astype(np.float32))
        p = np.asarray(m.predict_encoded(ht))
        assert p.shape == (64,) and (0 <= p).all() and (p < C).all()
        print("OK")
    """), timeout=900)
