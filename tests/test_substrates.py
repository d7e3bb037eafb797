"""Substrate tests: data determinism, checkpoint fault tolerance,
trainer resume-determinism, serving engine, optimizer."""
import dataclasses
import os
import shutil
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.configs.shapes import ShapeCell
from repro.data.tokens import SyntheticTokens
from repro.checkpoint import save_checkpoint, restore_checkpoint, latest_step
from repro.launch.mesh import make_host_mesh
from repro.train import Trainer, TrainConfig
from repro.optim import adamw_init, adamw_update
from repro.optim.schedule import cosine_schedule, wsd_schedule


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_stateless_addressing():
    d = SyntheticTokens(vocab=1000, seq_len=64, global_batch=8, seed=3)
    a1, b1 = d.batch_at(step=5)
    a2, b2 = d.batch_at(step=5)
    assert np.array_equal(a1, a2) and np.array_equal(b1, b2)
    a3, _ = d.batch_at(step=6)
    assert not np.array_equal(a1, a3)
    # host slicing matches the global batch
    lo, hi = 2, 5
    s1, _ = d.batch_at(5, lo, hi)
    assert np.array_equal(s1, a1[lo:hi])
    # targets are inputs shifted by one
    assert np.array_equal(a1[:, 1:], b1[:, :-1])


def test_data_prefetch():
    d = SyntheticTokens(vocab=100, seq_len=16, global_batch=2, seed=0)
    it = d.prefetch(start_step=3, depth=2)
    s, (tok, tgt) = next(it)
    assert s == 3 and tok.shape == (2, 16)
    s, _ = next(it)
    assert s == 4


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(5, dtype=jnp.float32),
            "b": [jnp.ones((2, 2)), jnp.int32(7)]}
    save_checkpoint(str(tmp_path), 10, tree)
    like = jax.tree.map(lambda x: x, tree)
    out, step = restore_checkpoint(str(tmp_path), like)
    assert step == 10
    assert np.array_equal(np.asarray(out["a"]), np.arange(5))
    assert int(out["b"][1]) == 7


def test_checkpoint_gc_and_latest(tmp_path):
    tree = {"x": jnp.zeros(3)}
    for s in (1, 2, 3, 4, 5):
        save_checkpoint(str(tmp_path), s, tree, keep=2)
    assert latest_step(str(tmp_path)) == 5
    kept = sorted(p.name for p in tmp_path.glob("step_*"))
    assert kept == ["step_4", "step_5"]


def test_checkpoint_structure_mismatch(tmp_path):
    save_checkpoint(str(tmp_path), 1, {"x": jnp.zeros(3)})
    with pytest.raises(ValueError):
        restore_checkpoint(str(tmp_path), {"y": {"z": jnp.zeros(3)}})


def test_checkpoint_atomic_publish(tmp_path):
    """A leftover .tmp dir (simulated crash) must not break save/restore."""
    (tmp_path / ".tmp_step_7").mkdir()
    save_checkpoint(str(tmp_path), 7, {"x": jnp.ones(2)})
    out, step = restore_checkpoint(str(tmp_path), {"x": jnp.zeros(2)})
    assert step == 7 and float(np.asarray(out["x"]).sum()) == 2.0


# ---------------------------------------------------------------------------
# trainer: loss decreases + resume determinism (fault tolerance)
# ---------------------------------------------------------------------------

def _tiny_cfg():
    base = get_smoke_config("qwen3-14b")
    return dataclasses.replace(base, n_layers=2, d_model=64, n_heads=4,
                               n_kv_heads=2, head_dim=16, d_ff=128,
                               vocab=256, remat=False)


def test_trainer_loss_decreases(tmp_path):
    cfg = _tiny_cfg()
    mesh = make_host_mesh(1, 1)
    cell = ShapeCell("t", "train", 32, 4)
    tr = Trainer(cfg, mesh, cell, TrainConfig(
        steps=30, ckpt_every=100, ckpt_dir=None, lr=1e-3, log_every=5))
    tr.init_or_restore()
    hist = tr.run()
    assert hist[-1]["ce"] < hist[0]["ce"]
    assert np.isfinite(hist[-1]["loss"])


def test_trainer_resume_determinism(tmp_path):
    """train 10 == train 6 + crash + resume 4 (bitwise metrics)."""
    cfg = _tiny_cfg()
    mesh = make_host_mesh(1, 1)
    cell = ShapeCell("t", "train", 32, 4)

    d1 = str(tmp_path / "a")
    tr = Trainer(cfg, mesh, cell, TrainConfig(
        steps=10, ckpt_every=100, ckpt_dir=d1, lr=1e-3, log_every=1))
    tr.init_or_restore()
    h_full = tr.run()
    loss_full = h_full[-1]["loss"]

    d2 = str(tmp_path / "b")
    tr = Trainer(cfg, mesh, cell, TrainConfig(
        steps=6, ckpt_every=6, ckpt_dir=d2, lr=1e-3, log_every=1))
    tr.init_or_restore()
    tr.run()
    # simulated crash: fresh Trainer object, restore from checkpoint
    tr2 = Trainer(cfg, mesh, cell, TrainConfig(
        steps=10, ckpt_every=100, ckpt_dir=d2, lr=1e-3, log_every=1))
    assert tr2.init_or_restore(), "should resume from checkpoint"
    assert tr2.step == 6
    h_res = tr2.run()
    assert abs(h_res[-1]["loss"] - loss_full) < 1e-5, \
        (h_res[-1]["loss"], loss_full)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_converges_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    opt = adamw_init(params)
    target = jnp.array([1.0, 2.0])
    for _ in range(200):
        g = jax.grad(lambda p: jnp.sum((p["w"] - target) ** 2))(params)
        params, opt, _ = adamw_update(g, opt, params, lr=5e-2,
                                      weight_decay=0.0)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=0.05)


def test_schedules():
    import numpy as np
    s = np.array([cosine_schedule(jnp.int32(i), peak_lr=1.0, warmup=10,
                                  total=100) for i in (0, 5, 10, 100)])
    assert s[0] == 0 and abs(s[2] - 1.0) < 1e-6 and s[3] < 0.2
    w = wsd_schedule(jnp.int32(50), peak_lr=1.0, warmup=10, total=100)
    assert abs(float(w) - 1.0) < 1e-6


# ---------------------------------------------------------------------------
# dry-run integration (subprocess with 8 forced host devices)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_dryrun_mini_mesh():
    """Lower+compile a reduced config against an 8-device forced-host mesh
    in a subprocess (device count locks at first jax init)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import dataclasses, jax
from repro.configs import get_smoke_config
from repro.configs.shapes import ShapeCell
from repro.distributed.steps import make_train_step, make_abstract_inputs
from repro.configs.shapes import input_specs

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = dataclasses.replace(get_smoke_config("qwen3-14b"), d_model=64,
                          n_heads=8, n_kv_heads=4, head_dim=16,
                          d_ff=256, vocab=1024)
cell = ShapeCell("mini", "train", 128, 8)
step, in_sh, out_sh = make_train_step(cfg, mesh, cell, grad_accum=2)
params, opt = make_abstract_inputs(cfg, mesh, cell)
sp = input_specs(cfg, cell)
c = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh).lower(
    params, opt, sp["tokens"], sp["targets"]).compile()
print("OK", c.memory_analysis().temp_size_in_bytes)
"""
    out = subprocess.run([sys.executable, "-c", code], cwd="/root/repo",
                         capture_output=True, text=True, timeout=300)
    assert "OK" in out.stdout, out.stderr[-2000:]
