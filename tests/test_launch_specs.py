"""Dry-run spec builder: every (arch x shape) cell must produce coherent
ShapeDtypeStructs + shardings on a production-shaped mesh WITHOUT allocating
(pure eval_shape), and the analytic roofline must be self-consistent.

Runs in a subprocess with 8 host devices and a (2,2,2) pod x data x model
mesh so divisibility-guard logic is exercised; full 256/512-way compiles are
covered by launch/dryrun.py itself."""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _run(body: str, timeout=900):
    script = ("import os\n"
              "os.environ['XLA_FLAGS'] = "
              "'--xla_force_host_platform_device_count=8'\n"
              f"import sys; sys.path.insert(0, {SRC!r})\n" + body)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0 and "OK" in out.stdout, \
        (out.stdout[-1500:], out.stderr[-3000:])


def test_cell_specs_build_for_all_cells():
    _run(textwrap.dedent("""
        import jax
        from repro.configs import ARCH_NAMES, get_config
        from repro.configs.base import SHAPES
        from repro.launch.mesh import make_mesh
        from repro.launch.specs import cell_specs
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        built = 0
        for arch in ARCH_NAMES:
            cfg = get_config(arch)
            for sname, shape in SHAPES.items():
                if sname == "long_500k" and not cfg.run_long_context:
                    continue
                fn, specs, outs, donate = cell_specs(cfg, shape, mesh)
                # every input leaf is an unallocated struct with a sharding
                for leaf in jax.tree.leaves(specs):
                    assert isinstance(leaf, jax.ShapeDtypeStruct), leaf
                built += 1
        assert built == 32, built
        print("OK", built)
    """))


def test_analytic_flops_sane():
    from repro.configs import ARCH_NAMES, get_config
    from repro.configs.base import SHAPES
    from repro.launch.roofline import analytic_flops
    for arch in ARCH_NAMES:
        cfg = get_config(arch)
        tr = analytic_flops(cfg, SHAPES["train_4k"])
        de = analytic_flops(cfg, SHAPES["decode_32k"])
        # train total = 3x forward; decode works on 1 token/seq
        assert tr["total"] == pytest.approx(3 * tr["fwd"])
        assert de["tokens"] == SHAPES["decode_32k"].global_batch
        assert tr["total"] > de["total"]
        # useful-compute ratio in (0, 1.05]
        r = tr["model_flops"] / tr["total"]
        assert 0.05 < r <= 1.05, (arch, r)


def test_collective_parser():
    from repro.launch.dryrun import collective_bytes
    hlo = """
      %ag = bf16[8,128]{1,0} all-gather(%x), replica_groups={}
      %ar.1 = f32[16,16]{1,0} all-reduce(%y), to_apply=%sum
      %cp = u8[4]{0} collective-permute(%z)
      %other = f32[2,2]{1,0} add(%a, %b)
    """
    out = collective_bytes(hlo)
    assert out["counts"] == {"all-gather": 1, "all-reduce": 1,
                             "collective-permute": 1}
    assert out["bytes"]["all-gather"] == 8 * 128 * 2
    assert out["bytes"]["all-reduce"] == 16 * 16 * 4
    assert out["total_bytes"] == 8 * 128 * 2 + 16 * 16 * 4 + 4


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache goes to the checkout's fixed .jax_cache directory."""
    import jax
    from repro.launch.compile_cache import CHECKOUT, enable_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = enable_compile_cache()
        assert path == os.path.join(str(CHECKOUT), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert os.path.isdir(os.path.join(str(CHECKOUT), "src", "repro"))
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
