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
import re

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
C_LFAT, N_LFAT = 1_305_265, 21    # LF-AmazonTitles-1.3M's classes, k = 2


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


def _compile_loghd_predict(one_chip, monkeypatch, c, n, rows=64):
    """The LogHD predict executable that serving dispatches on the chip:
    bundle_sim then profile_decode, both as kernels, for C classes and n
    bundles.  The kernels ask the default backend (the CPU here) whether to
    interpret, so this tells them they are on a TPU, and drops traces made
    before and after."""
    from repro.kernels import common
    monkeypatch.setattr(common, "interpret", lambda: False)
    jax.clear_caches()
    model = LogHDModel(
        enc={"proj": _s((617, D)), "bias": _s((D,)), "center": _s((D,))},
        bundles=_s((n, D)), profiles=_s((c, n)),
        codebook=_s((c, n), jnp.int32))
    predict = dispatch.predict_fn(model, use_kernels=True)
    try:
        return _compile_for_chip(predict, one_chip, model, _s((rows, D)))
    finally:
        jax.clear_caches()


def test_loghd_kernel_predict_compiles(one_chip, monkeypatch):
    hlo = _compile_loghd_predict(one_chip, monkeypatch, 26, N_ISOLET)
    assert hlo.count("tpu_custom_call") >= 2


def _entry_ops(hlo):
    """The ENTRY computation's instructions: name -> (type, opcode,
    operand names)."""
    entry = hlo[hlo.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    ops = {}
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\((.*?)\)(?:, |$)",
                     line)
        if m:
            ops[m[1]] = (m[2], m[3], re.findall(r"%([\w.-]+)", m[4]))
    return ops


def _table_paths(hlo, table_type):
    """Every chain of ops from the ENTRY parameter typed ``table_type`` to
    the first op that is not a bitcast or a copy: [(name, opcode, type),
    ...] each, the op it ends in last."""
    ops = _entry_ops(hlo)
    (param,) = [k for k, (t, op, _) in ops.items()
                if op == "parameter" and t.startswith(table_type + "{")]
    paths, todo = [], [(param, [])]
    while todo:
        name, path = todo.pop()
        for user, (t, op, args) in ops.items():
            if name in args:
                step = path + [(user, op, t)]
                if op in ("bitcast", "copy-start", "copy-done"):
                    todo.append((user, step))
                else:
                    paths.append(step)
    return paths


def _table_shaped(hlo, c, n):
    """pad, copy and transpose results shaped like the profile table or its
    padding: a dimension from C to C plus one 8,192-lane tile, beside n or
    128."""
    found = []
    for t, op, _ in _entry_ops(hlo).values():
        if op not in ("pad", "copy", "transpose"):
            continue
        for dims in re.findall(r"\[(\d+),(\d+)\]", t):
            for a, b in (dims, dims[::-1]):
                if c <= int(a) < c + 8192 and int(b) in (n, 128):
                    found.append((op, t))
    return found


def test_loghd_kernel_predict_reads_lfat_table_in_place(one_chip, monkeypatch):
    """At LF-AmazonTitles-1.3M's shape the profile table reaches the
    profile_decode kernel as a bitcast of the stored parameter: no per-call
    relayout copy and no pad of the table to 128 lanes."""
    hlo = _compile_loghd_predict(one_chip, monkeypatch, C_LFAT, N_LFAT)
    assert _table_shaped(hlo, C_LFAT, N_LFAT) == []
    (path,) = _table_paths(hlo, f"f32[{C_LFAT},{N_LFAT}]")
    assert [op for _, op, _ in path] == ["bitcast", "custom-call"]
    kernel, _, _ = path[-1]
    assert kernel.startswith("profile_decode_scores")
    assert 'custom_call_target="tpu_custom_call"' in next(
        line for line in hlo.splitlines() if f"%{kernel} = " in line)


def test_loghd_kernel_predict_isolet_table_not_relaid(one_chip, monkeypatch):
    """At ISOLET's shape the (26, 10) table may be moved into fast memory
    whole, but keeps its class-minor layout and is not padded on its way to
    the kernel."""
    hlo = _compile_loghd_predict(one_chip, monkeypatch, 26, N_ISOLET)
    assert _table_shaped(hlo, 26, N_ISOLET) == []
    table = f"f32[26,{N_ISOLET}]"
    (path,) = _table_paths(hlo, table)
    assert path[-1][0].startswith("profile_decode_scores")
    for _, op, t in path[:-1]:
        assert op == "bitcast" or table + "{0,1:" in t, (op, t)
