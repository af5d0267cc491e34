"""RWKV-6 "Finch" as published: ln0 on the embeddings, ln_x's eps, an
unclipped decay, the 7B LoRA widths, the named scopes that mark its
parts in the compiled program, and the store's counter of the bytes
its dense per-slot state copies move."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import registry
from repro.models import kv_cache, model_zoo, rwkv6
from repro.models import transformer as T

SMOKE = registry.get_config("rwkv6-7b", smoke=True)


def _params(cfg=SMOKE, seed=0):
    return model_zoo.build(cfg).init(jax.random.PRNGKey(seed))


def _logits(params, cfg, tokens):
    h, _, _ = T.decoder_forward(params, cfg, tokens)
    return T.logits_from_hidden(params, cfg, h).astype(jnp.float32)


def test_only_rwkv_has_an_embedding_norm():
    assert "embed_norm" in T.decoder_specs(SMOKE)
    glm = registry.get_config("glm4-9b", smoke=True)
    assert "embed_norm" not in T.decoder_specs(glm)


def test_ln0_normalises_the_embeddings():
    # A LayerNorm right after the lookup makes the model blind to the
    # embedding table's scale; without it the logits follow the scale.
    cfg = dataclasses.replace(SMOKE, compute_dtype=jnp.float32)
    params = _params(cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 12)), jnp.int32)
    scaled = dict(params, embed={"table": params["embed"]["table"] * 8})
    a, b = _logits(params, cfg, tokens), _logits(scaled, cfg, tokens)
    # f32 throughout; only the eps (1e-5 against a variance of ~1e-3
    # and ~6e-2) separates the two.
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)
    bare = {k: v for k, v in params.items() if k != "embed_norm"}
    bare_scaled = dict(bare, embed=scaled["embed"])
    assert float(jnp.max(jnp.abs(_logits(bare, cfg, tokens)
                                 - _logits(bare_scaled, cfg, tokens)))) \
        > 1e-2


def test_ln_x_eps_is_the_published_one():
    assert rwkv6.LN_X_EPS == pytest.approx(1e-5 * 8 ** 2)
    # One head of four channels with variance 1e-3: the eps is
    # comparable to it, so the published 6.4e-4 and 1e-5 differ.
    y = jnp.asarray([[[0.0, 0.02, 0.04, 0.06]]], jnp.float32) * np.sqrt(
        1e-3 / 5e-4)
    var = float(jnp.var(y))
    out = rwkv6._group_norm(y, jnp.ones(4), jnp.zeros(4), 1)
    want = (y - jnp.mean(y)) / np.sqrt(var + 6.4e-4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5)


def test_the_decay_is_not_clipped():
    # No k v term (wk = wv = 0), so the step only decays the state:
    # S_1 = w S_0.  A decay exponent of -20 gives w = exp(-exp(-20)),
    # 1 in f32; a clip at -10 would have decayed it by 4.5e-5.
    cfg = dataclasses.replace(SMOKE, compute_dtype=jnp.float32)
    p = jax.tree_util.tree_map(
        lambda a: a[0], _params(cfg)["stacks"]["S0"]["L0"]["attn"])
    p = dict(p, wk=jnp.zeros_like(p["wk"]), wv=jnp.zeros_like(p["wv"]),
             decay_base=jnp.full_like(p["decay_base"], -20.0),
             decay_w2=jnp.zeros_like(p["decay_w2"]))
    state = rwkv6.make_state(cfg, 1)
    s0 = jax.random.normal(jax.random.PRNGKey(1), state["wkv"].shape)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 1, cfg.d_model))
    _, new = rwkv6.time_mix(p, cfg, x, dict(state, wkv=s0))
    np.testing.assert_array_equal(np.asarray(new["wkv"]), np.asarray(s0))


def test_full_config_has_the_7b_lora_widths_and_size():
    cfg = registry.get_config("rwkv6-7b")
    assert (cfg.rwkv.lora_mix, cfg.rwkv.lora_decay) == (64, 128)
    assert (SMOKE.rwkv.lora_mix, SMOKE.rwkv.lora_decay) == (8, 8)
    model = model_zoo.build(cfg)
    attn = model.param_shapes()["stacks"]["S0"]["L0"]["attn"]
    assert attn["maa_w1"].shape == (32, 4096, 320)
    assert attn["maa_w2"].shape == (32, 5, 64, 4096)
    assert attn["decay_w1"].shape == (32, 4096, 128)
    assert attn["decay_w2"].shape == (32, 128, 4096)
    # The published 7.64 B: 32 layers of 221.84 M, embedding and head
    # of 268.4 M each, ln0 and ln_out.
    assert model.num_params() == 32 * 221_839_360 + 2 * 65536 * 4096 \
        + 4 * 4096


def test_the_three_scopes_are_in_the_compiled_prefill():
    model = model_zoo.build(SMOKE)
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((1, 80), jnp.int32)}
    text = jax.jit(model.prefill).lower(params, batch).compile().as_text()
    paths = [line.split('op_name="', 1)[1].split('"', 1)[0]
             for line in text.splitlines() if 'op_name="' in line]
    assert any("rwkv.time_mix/rwkv.wkv/while" in p for p in paths)
    assert any("rwkv.channel_mix" in p for p in paths)
    # The recurrence sits inside the time-mix, never beside it.
    assert all("rwkv.time_mix/rwkv.wkv" in p for p in paths
               if "rwkv.wkv" in p)


def _store(cfg=SMOKE, slots=2):
    template = jax.eval_shape(lambda: T.init_decoder_cache(cfg, slots,
                                                           64, 0))
    return kv_cache.PagedKVCache(template, num_slots=slots, page_size=16,
                                 quant="none"), template


def test_state_bytes_count_the_leaves_one_write_token_copies():
    store, template = _store()
    caches = jax.tree_util.tree_map(
        lambda s: jnp.ones(s.shape, s.dtype), template)
    store.alloc_slot(1)
    obs.reset()
    store.write_token(caches, 1, 5)
    got = obs.counters()["store.state_bytes"]
    # Every leaf of the recurrent state is dense and per slot: the
    # (layers, slots, heads, 16, 16) f32 WKV state and the two bf16
    # token-shift rows, each copied whole by the eager update.
    layers, n = SMOKE.num_layers, SMOKE.d_model // 16
    want = {"S0/L0/wkv": layers * 2 * n * 16 * 16 * 4,
            "S0/L0/x_tm": layers * 2 * SMOKE.d_model * 2,
            "S0/L0/x_cm": layers * 2 * SMOKE.d_model * 2}
    assert got == want
    store.write_slot(1, jax.tree_util.tree_map(lambda a: a[:, :1],
                                               caches))
    assert obs.counters()["store.state_bytes"] == {
        k: 2 * v for k, v in want.items()}
    obs.reset()


def test_the_state_counter_costs_no_device_op(monkeypatch):
    store, template = _store()
    caches = jax.tree_util.tree_map(
        lambda s: jnp.ones(s.shape, s.dtype), template)
    store.alloc_slot(0)
    seen = []
    monkeypatch.setattr(obs, "count",
                        lambda name, key, n=1: seen.append(n))
    # No device-to-host transfer (no sync), and the count is a host
    # integer taken from the leaf's shape, not an array.
    with jax.transfer_guard_device_to_host("disallow"):
        store.write_token(caches, 0, 3)
    assert len(seen) == 3 and all(type(n) is int for n in seen)
