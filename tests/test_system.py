"""End-to-end behaviour tests through the public API: train a tiny LM on
the synthetic pipeline, serve it with batched prefill+decode, and resume
from checkpoint — the full production loop in miniature."""

import os

import jax
import numpy as np
import pytest

from repro.launch import train as trainlib
from repro.launch.serve import Server
from repro.models import model_zoo


def test_train_serve_resume_loop(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    # 8 steps with a save at step 5
    state, hist = trainlib.run(
        "gemma2-2b", steps=8, smoke=True, batch_override=4,
        seq_override=32, ckpt_dir=ckpt, log_every=4, save_every=5)
    assert all(np.isfinite(l) for _, l in hist)

    # resume: a fresh invocation continues from the checkpoint
    state2, hist2 = trainlib.run(
        "gemma2-2b", steps=10, smoke=True, batch_override=4,
        seq_override=32, ckpt_dir=ckpt, log_every=2, save_every=5)
    assert int(state2.step) == 10

    # serve the trained weights
    from repro.configs import registry
    cfg = registry.get_config("gemma2-2b", smoke=True)
    model = model_zoo.build(cfg)
    srv = Server(model)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (4, 8)).astype(np.int32)
    toks = srv.generate(state2.params, prompts, max_new=4)
    assert toks.shape == (4, 4)
    assert (toks >= 0).all() and (toks < cfg.vocab_size).all()


def test_reduction_engine_is_default_everywhere():
    """The paper's technique must be on by default in the stack."""
    from repro.configs import registry
    for arch in registry.list_archs():
        assert registry.get_config(arch).reduce_method == "mma"


@pytest.mark.parametrize("env_set", [False, True])
def test_compile_cache_dir(monkeypatch, tmp_path, env_set):
    """A set JAX_COMPILATION_CACHE_DIR is left to JAX (nothing is set);
    otherwise the cache is pinned to .jax_cache/ at the checkout root."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        if env_set:
            d = str(tmp_path / "cache")
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
            assert compile_cache.use_compile_cache() == d
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            got = compile_cache.use_compile_cache()
            assert got == compile_cache.DEFAULT_DIR
            assert jax.config.jax_compilation_cache_dir == got
            assert os.path.basename(got) == ".jax_cache"
            assert os.path.isfile(os.path.join(
                compile_cache.CHECKOUT_ROOT, "pyproject.toml"))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
