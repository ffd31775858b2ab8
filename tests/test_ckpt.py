"""Fault tolerance: checkpoint/restart, integrity, FZ codec, resume."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import checkpoint as ckpt


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.standard_normal((128, 64)).astype(np.float32)),
        "b": jnp.asarray(rng.standard_normal((64,)).astype(np.float32)),
        "emb": jnp.asarray(rng.standard_normal((1000, 128))).astype(jnp.bfloat16),
        "count": jnp.int32(17),
    }


def test_save_restore_bitwise(tmp_path):
    t = _tree()
    ckpt.save(str(tmp_path), 5, t, meta={"foo": 1})
    restored, meta = ckpt.restore(str(tmp_path), t)
    assert meta["step"] == 5 and meta["foo"] == 1
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_pointer_and_gc(tmp_path):
    t = _tree()
    for s in (1, 2, 3, 4, 5):
        ckpt.save(str(tmp_path), s, t, keep_last=2)
    assert ckpt.latest_step(str(tmp_path)) == 5
    kept = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert kept == ["step_00000004", "step_00000005"]


def test_corruption_detected(tmp_path):
    t = _tree()
    d = ckpt.save(str(tmp_path), 1, t)
    victim = os.path.join(d, "leaf_000000.bin")
    raw = bytearray(open(victim, "rb").read())
    raw[10] ^= 0xFF
    open(victim, "wb").write(bytes(raw))
    with pytest.raises(IOError, match="checksum"):
        ckpt.restore(str(tmp_path), t)


def test_fz_codec_error_bounded(tmp_path):
    rng = np.random.default_rng(3)
    big = np.cumsum(rng.standard_normal((512, 256)).astype(np.float32), axis=0)
    t = {"big": jnp.asarray(big), "small": jnp.ones((8,), jnp.float32)}
    ckpt.save(str(tmp_path), 1, t, codec="fz")
    restored, _ = ckpt.restore(str(tmp_path), t)
    rng_ = big.max() - big.min()
    err = np.abs(np.asarray(restored["big"]) - big).max()
    # 1.01x + ulp slack: f32 multiply/rint/multiply rounding at q ~ 5e4
    assert err <= 1e-5 * rng_ * 1.01 + rng_ * 2e-7, err
    np.testing.assert_array_equal(np.asarray(restored["small"]), np.ones(8, np.float32))
    rep = ckpt.compression_report(str(tmp_path), 1)
    assert rep["ratio"] > 1.5, rep


def test_atomicity_partial_write_ignored(tmp_path):
    """A stale tmp dir (simulated crash) never shadows a published step."""
    t = _tree()
    ckpt.save(str(tmp_path), 1, t)
    os.makedirs(os.path.join(str(tmp_path), ".tmp_step_00000002"))
    assert ckpt.latest_step(str(tmp_path)) == 1
    restored, meta = ckpt.restore(str(tmp_path), t)
    assert meta["step"] == 1


def test_trainer_resume_bitwise(tmp_path):
    """Restart from checkpoint reproduces the exact loss sequence."""
    from repro import configs
    from repro.configs.base import ShapeConfig
    from repro.data.tokens import TokenStream
    from repro.launch.mesh import make_local_mesh
    from repro.models import zoo
    from repro.train import TrainConfig, Trainer

    cfg = configs.get("yi-6b", smoke=True)
    model = zoo.build(cfg)
    shape = ShapeConfig("tiny", 32, 4, "train")
    mesh = make_local_mesh()
    stream = TokenStream(vocab_size=cfg.vocab, seq_len=32, global_batch=4, seed=7)

    t1 = Trainer(model, shape, mesh, TrainConfig(), stream=stream,
                 ckpt_dir=str(tmp_path), ckpt_every=100)
    t1.run(4)
    t2 = Trainer(model, shape, mesh, TrainConfig(), stream=stream,
                 ckpt_dir=str(tmp_path), ckpt_every=100)
    assert t2.step == 4
    h2 = t2.run(2)
    t3 = Trainer(model, shape, mesh, TrainConfig(), stream=stream, ckpt_dir=None)
    h3 = t3.run(6)
    ref = [m["loss"] for m in h3][4:]
    got = [m["loss"] for m in h2]
    np.testing.assert_allclose(got, ref, rtol=0, atol=0)


def test_straggler_watchdog_flags_injected_delay(tmp_path):
    from repro.train.trainer import StragglerWatchdog
    wd = StragglerWatchdog(factor=3.0, warmup=1)
    wd.observe(0, 10.0)   # warmup (compile step)
    wd.observe(1, 0.1)
    wd.observe(2, 0.11)
    ev = wd.observe(3, 1.0)
    assert ev is not None and ev.step == 3
    assert wd.observe(4, 0.1) is None
