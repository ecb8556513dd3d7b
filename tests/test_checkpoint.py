"""Checkpoint system: atomic commit, async writes, restart-exact resume,
elastic restore onto a different mesh (subprocess with 8 host devices)."""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.ckpt import (AsyncCheckpointer, latest_step,
                                   restore_checkpoint, save_checkpoint)


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (16, 8)),
            "b": {"w": jax.random.normal(k, (4, 4)).astype(jnp.bfloat16),
                  "step": 7}}


def test_roundtrip(tmp_path):
    tree = _tree()
    save_checkpoint(str(tmp_path), 3, tree)
    assert latest_step(str(tmp_path)) == 3
    out = restore_checkpoint(str(tmp_path), 3, tree)
    np.testing.assert_array_equal(out["a"], tree["a"])
    np.testing.assert_array_equal(
        np.asarray(out["b"]["w"].astype(jnp.float32)),
        np.asarray(tree["b"]["w"].astype(jnp.float32)))
    assert out["b"]["step"] == 7
    assert out["b"]["w"].dtype == jnp.bfloat16


def test_atomicity_no_commit_invisible(tmp_path):
    tree = _tree()
    save_checkpoint(str(tmp_path), 5, tree)
    os.remove(tmp_path / "step_000000005" / "COMMIT")
    assert latest_step(str(tmp_path)) is None
    with pytest.raises(FileNotFoundError):
        restore_checkpoint(str(tmp_path), 5, tree)


def test_async_checkpointer(tmp_path):
    tree = _tree(1)
    ck = AsyncCheckpointer(str(tmp_path))
    ck.save(10, tree)
    ck.wait()
    out = restore_checkpoint(str(tmp_path), 10, tree)
    np.testing.assert_array_equal(out["a"], tree["a"])
    np.testing.assert_allclose(
        np.asarray(out["b"]["w"].astype(np.float32)),
        np.asarray(tree["b"]["w"].astype(np.float32)))


def test_structure_mismatch_raises(tmp_path):
    save_checkpoint(str(tmp_path), 1, _tree())
    with pytest.raises(ValueError):
        restore_checkpoint(str(tmp_path), 1, {"only": jnp.zeros((2,))})


def test_train_restart_exact(tmp_path):
    """Training 8 steps straight == training 4, 'crashing', resuming 4."""
    from repro.configs import get_smoke_config
    from repro.runtime.train_loop import TrainLoopConfig, run_training

    cfg = dataclasses.replace(get_smoke_config("qwen3-1.7b"), n_periods=1,
                              vocab=128, d_model=32, n_heads=2, n_kv_heads=2,
                              head_dim=16, d_ff=64)
    # run A: continuous
    loop_a = TrainLoopConfig(total_steps=8, ckpt_dir=str(tmp_path / "a"),
                             ckpt_every=100, warmup_steps=2, log_every=100)
    out_a = run_training(cfg, loop=loop_a, global_batch=4, seq_len=32)
    # run B: same 8-step schedule, 'crash' after step 4, resume
    loop_b = TrainLoopConfig(total_steps=8, ckpt_dir=str(tmp_path / "b"),
                             ckpt_every=100, warmup_steps=2, log_every=100)
    run_training(cfg, loop=loop_b, global_batch=4, seq_len=32, stop_after=4)
    out_b = run_training(cfg, loop=loop_b, global_batch=4, seq_len=32)
    assert out_b["resumed"] and out_b["first_step"] == 4
    # identical final losses (deterministic pipeline + exact state restore)
    np.testing.assert_allclose(out_a["losses"][-1], out_b["losses"][-1],
                               rtol=1e-5)


ELASTIC_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "{src}")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint.ckpt import save_checkpoint, restore_checkpoint
    from repro.launch.mesh import make_mesh

    tree = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
    mesh1 = make_mesh((2, 2), ("data", "model"), jax.devices()[:4])
    sh1 = {{"w": NamedSharding(mesh1, P("data", "model"))}}
    placed = jax.device_put(tree, sh1)
    save_checkpoint("{ckpt}", 1, placed)

    # restore onto a DIFFERENT mesh shape and device count
    mesh2 = make_mesh((8, 1), ("data", "model"))
    sh2 = {{"w": NamedSharding(mesh2, P("data", "model"))}}
    out = restore_checkpoint("{ckpt}", 1, tree, sh2)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
    assert len(out["w"].sharding.device_set) == 8
    print("ELASTIC_OK")
""")


def test_elastic_reshard_subprocess(tmp_path):
    script = ELASTIC_SCRIPT.format(
        src=os.path.join(os.path.dirname(__file__), "..", "src"),
        ckpt=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=300)
    assert "ELASTIC_OK" in out.stdout, out.stderr[-2000:]
