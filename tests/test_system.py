"""End-to-end behaviour tests for the whole system: train -> checkpoint
-> preempt/restart -> serve, on the quickstart arch."""
import numpy as np

from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.launch.serve import ServeConfig, Server
from repro.launch.train import TrainConfig, Trainer
from repro.optim.adamw import AdamWConfig


def test_train_restart_serve_roundtrip(tmp_path):
    cfg = get_config("quickstart", smoke=True)
    tcfg = TrainConfig(steps=8, log_every=100, ckpt_every=4,
                       ckpt_dir=str(tmp_path),
                       optimizer=AdamWConfig(lr=1e-3, total_steps=8))

    def pipe():
        return SyntheticPipeline(DataConfig(
            vocab_size=cfg.vocab_size, seq_len=32, global_batch=4))

    # phase 1: train to step 8 (checkpoints at 4 and exit)
    t1 = Trainer(cfg, tcfg)
    params1, _, hist1 = t1.run(pipe())
    assert len(hist1) == 8

    # phase 2: restart -- must resume at 8, train 4 more
    tcfg2 = TrainConfig(steps=12, log_every=100, ckpt_dir=str(tmp_path),
                        optimizer=AdamWConfig(lr=1e-3, total_steps=12))
    p2 = pipe()
    t2 = Trainer(cfg, tcfg2)
    step, params2, _ = t2.restore_or_init(p2)
    assert step == 8
    params2, _, hist2 = t2.run(p2)
    assert len(hist2) == 4  # only the remaining steps

    # phase 3: serve from the final checkpoint
    from repro.checkpoint.manager import CheckpointManager
    from repro.models import abstract_init
    mgr = CheckpointManager(str(tmp_path))
    _, params, _, _ = mgr.restore(None, abstract_init(cfg))
    server = Server(cfg, params, ServeConfig(max_len=48, temperature=0.0))
    out = server.generate(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16)),
        max_new=4)
    assert out.shape == (2, 4)
    assert (out >= 0).all() and (out < cfg.padded_vocab).all()


def test_loss_improves_on_learnable_data(tmp_path):
    cfg = get_config("quickstart", smoke=True)
    tcfg = TrainConfig(steps=25, log_every=100, ckpt_dir=str(tmp_path),
                       optimizer=AdamWConfig(lr=5e-3, warmup_steps=3,
                                             total_steps=25))
    pipe = SyntheticPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                        seq_len=64, global_batch=4))
    _, _, hist = Trainer(cfg, tcfg).run(pipe)
    assert np.mean([h["loss"] for h in hist[-5:]]) < \
        np.mean([h["loss"] for h in hist[:5]])


def test_compile_cache_env_var_configures_nothing_else(monkeypatch,
                                                       tmp_path):
    import jax
    from repro.launch import cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cache.ENV, str(tmp_path))
    assert cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_inside_checkout(monkeypatch):
    import os
    import subprocess
    import sys
    import jax
    from repro.launch import cache
    monkeypatch.delenv(cache.ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
        assert cache.enable_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    # another process (another pid, time and temporary directory)
    # derives the same path, so it finds what this one compiled
    env = dict(os.environ, TMPDIR=os.path.join(root, "src"))
    other = subprocess.run(
        [sys.executable, "-c", "from repro.launch.cache import "
         "CHECKOUT_DIR; print(CHECKOUT_DIR)"], env=env,
        capture_output=True, text=True, timeout=120, check=True)
    assert other.stdout.strip() == path
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
