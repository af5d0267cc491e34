"""The seeded checkpoint (bench/weights.py, bench/checkpoint.py) and its
loader into the program's layout (bench/drivers/serve.py): both sides
read the same numbers, and the program agrees with the plain reference
on a small model."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import checkpoint as C
from bench import run
from bench import weights as W

SEED = 2**32 + 17


def small_cfg():
    with open(os.path.join(run.BENCH, "configs", "glm4-9b.json")) as f:
        cfg = json.load(f)
    cfg.update(num_layers=2, hidden_size=64, num_attention_heads=4,
               multi_query_group_num=2, kv_channels=16,
               ffn_hidden_size=128, padded_vocab_size=512)
    return cfg


@pytest.fixture(scope="module")
def driver():
    return run.load_module(os.path.join(run.BENCH, "drivers", "serve.py"),
                           "t_w_serve")


@pytest.fixture(scope="module")
def ref():
    return run.load_module(os.path.join(run.BENCH, "configs",
                                        "glm4-9b.ref.py"), "t_w_ref")


def test_draws_depend_on_seed_name_layer_and_index_only():
    words = W.seed_words(SEED)
    a = C.tensor(words, "q_w", (8, 16), layer=3)
    assert np.array_equal(a, C.tensor(words, "q_w", (8, 16), layer=3))
    assert not np.array_equal(a, C.tensor(words, "q_w", (8, 16), layer=4))
    assert not np.array_equal(a, C.tensor(words, "k_w", (8, 16), layer=3))
    assert not np.array_equal(
        a, C.tensor(W.seed_words(SEED + 1), "q_w", (8, 16), layer=3))
    # A stacked draw (one key per layer) equals the per-layer draws.
    shape = (3, 8, 16)
    layer = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    idx = W.flat_index(shape[1:])[None] + jnp.zeros(shape, jnp.uint32)
    stacked = C.draw(words, "q_w", idx, layer)
    for i in range(3):
        assert np.array_equal(stacked[i],
                              C.tensor(words, "q_w", (8, 16), layer=i))


def test_draws_are_uniform_with_the_stated_width():
    x = np.asarray(C.tensor(W.seed_words(SEED), "gate_w", (256, 256)),
                   np.float64)
    assert abs(x.std() - C.MATRIX_STD) < 0.001
    assert abs(x.mean()) < 0.001
    with pytest.raises(ValueError):
        W.seed_words(-1)


def test_rotary_source_maps_half_split_pairs_to_interleaved():
    src = np.asarray(C.rotary_source(np.arange(16), 16, 8))
    assert src.tolist() == [0, 2, 4, 6, 1, 3, 5, 7,
                            8, 9, 10, 11, 12, 13, 14, 15]


def test_loader_places_every_checkpoint_tensor(driver):
    from repro.models import model_zoo
    cfg = small_cfg()
    model = model_zoo.build(driver.model_config(cfg))
    params = driver.load_params(cfg, model, SEED)
    words = W.seed_words(SEED)
    lay = params["stacks"]["S0"]["L0"]
    for layer in range(cfg["num_layers"]):
        w = C.tensors(words, C.layer_shapes(cfg).items(), layer)
        assert np.array_equal(lay["mlp"]["wi_gate"][layer], w["gate_w"])
        assert np.array_equal(lay["mlp"]["wo"][layer], w["down_w"])
        assert np.array_equal(lay["attn"]["wv"][layer].reshape(64, -1),
                              w["v_w"])
        assert np.array_equal(lay["attn"]["wo"][layer].reshape(-1, 64),
                              w["o_w"])
        assert np.array_equal(lay["pre_norm"]["scale"][layer],
                              w["attn_norm"])
        # q and k carry their rotary dims in the program's order.
        perm = np.asarray(C.rotary_source(np.arange(16), 16, 8))
        q = np.asarray(w["q_w"]).reshape(64, 4, 16)[:, :, perm]
        assert np.array_equal(lay["attn"]["wq"][layer], q)
    g = C.tensors(words, C.global_shapes(cfg).items())
    assert np.array_equal(params["lm_head"], g["head"])
    assert np.array_equal(params["embed"]["table"], g["embed"])
    assert all(x.dtype == jnp.bfloat16
               for x in jax.tree_util.tree_leaves(params))


def test_program_in_f32_agrees_with_the_reference(driver, ref):
    from repro.models import model_zoo
    cfg = small_cfg()
    mcfg = dataclasses.replace(driver.model_config(cfg),
                               compute_dtype=jnp.float32)
    model = model_zoo.build(mcfg)
    params = driver.load_params(cfg, model, SEED)
    toks = np.random.default_rng(0).integers(0, 512, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = model.logits(params, {"tokens": jnp.asarray(toks[None])})[0]
    words = W.seed_words(SEED)
    h = ref.hidden(cfg, words, toks)[:40]
    g = C.tensors(words, [(n, s) for n, s in C.global_shapes(cfg).items()
                          if n != "embed"])
    want = ref._logits(h, g["final_norm"], g["head"],
                       cfg_items=tuple(sorted(C.dims(cfg).items())),
                       quant=None)
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    # Left over: the program's norm epsilon (1e-6) against the
    # published 1.5625e-7, a few parts in 1e4 of the logits' spread.
    assert np.max(np.abs(got - want)) < 0.01 * np.std(want)
    assert np.array_equal(np.argmax(got, -1), np.argmax(want, -1))
