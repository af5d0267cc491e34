"""The rwkv6-7b cell's parts at a small size on the CPU: the plain
reference against the program's forward, prefill and decode through
ContinuousServer against the reference's full forward, the loader's
layout, a whole run of the harness, and the new readers.

The small configuration keeps the cell's kind of layer and changes
only sizes: width 256 in four heads of 64, channel-mix 512, two
layers, a vocabulary of 4,096, LoRAs of 16.  The registry's
``rwkv6-7b`` is swapped for the program at those sizes, since the
driver builds the registry's model."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops_rwkv6, peaks, run
from bench import trace as T
from repro.configs import registry
from repro.configs.base import RWKVConfig
from repro.models import model_zoo
from repro.models import transformer as T_model

SEED = 2**31 + 4099
SMALL = dataclasses.replace(
    registry.get_config("rwkv6-7b", smoke=True), d_model=256,
    num_heads=4, num_kv_heads=4, head_dim=64, d_ff=512, vocab_size=4096,
    num_layers=2, rwkv=RWKVConfig(head_size=64, lora_decay=16,
                                  lora_mix=16))
# Limit of the small cell's check, set from the CPU readings at these
# sizes by the cells' rule: the program's widest gap 0.0 over four
# seeds (its bf16 forward and the f32 reference agree on every pick),
# the fp8 control's smallest over the same seeds well above it.
SMALL_GAP_LIMIT = 0.01
# The state's limit, from CPU readings at these sizes: the program's
# first-layer state lies 0.0053-0.0058 from the reference's f32 state
# after prompts of 16 and 80 tokens (four seeds) and 0.0041 after 512
# (two); after 512 tokens the reference with a bf16 state reads
# 0.0145-0.0155 and with fp8 matmuls 0.043.
SMALL_STATE_LIMIT = 0.009
MIX = {"kind": "requests", "slots": 4, "capacity": 128, "page_size": 16,
       "prompt_buckets": [16, 80], "prompt_weights": [0.5, 0.5],
       "round": 2, "rounds": 50,
       "max_new": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                   "min": 8, "max": 48},
       "sampling": "greedy", "check": {"tokens": 100, "max_requests": 4}}


def _module(*parts, name):
    return run.load_module(os.path.join(run.BENCH, *parts), name)


DRIVER = _module("drivers", "serve_rwkv6.py", name="t_drv_rwkv6")
REF = _module("configs", "rwkv6-7b.ref.py", name="t_ref_rwkv6")


def small_cfg():
    with open(os.path.join(run.BENCH, "configs", "rwkv6-7b.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=256, attention_hidden_size=256,
               num_attention_heads=4, head_size=64, intermediate_size=512,
               num_hidden_layers=2, vocab_size=4096, time_mix_extra_dim=16,
               time_decay_extra_dim=16,
               limits={"max_logit_gap": SMALL_GAP_LIMIT,
                       "wkv_state_err": SMALL_STATE_LIMIT})
    return cfg


@pytest.fixture()
def small(monkeypatch):
    monkeypatch.setattr(registry, "get_config",
                        lambda name, smoke=False: SMALL)
    return small_cfg()


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 4096, n).astype(
        np.int32)


def _program(cfg, dtype=jnp.bfloat16):
    mc = DRIVER.model_config(cfg)
    model = model_zoo.build(dataclasses.replace(mc, compute_dtype=dtype))
    params = DRIVER.load_params(cfg, model, SEED)
    return model, jax.tree_util.tree_map(lambda a: a.astype(dtype)
                                         if dtype == jnp.float32 else a,
                                         params)


def _forward(model, params, tokens):
    with jax.default_matmul_precision("highest"):
        h, _, _ = T_model.decoder_forward(params, model.cfg,
                                          jnp.asarray(tokens[None]))
        return np.asarray(T_model.logits_from_hidden(
            params, model.cfg, h)[0], np.float32)


def test_the_driver_refuses_a_program_of_other_widths(monkeypatch):
    # The parent's 7B had LoRAs of 32 / 64: the run stops at set-up.
    monkeypatch.setattr(registry, "get_config", lambda name, smoke=False:
                        dataclasses.replace(SMALL, rwkv=RWKVConfig(
                            64, lora_decay=8, lora_mix=8)))
    with pytest.raises(ValueError, match="lora"):
        DRIVER.model_config(small_cfg())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_reference_matches_the_program_forward(small, dtype):
    model, params = _program(small, jnp.dtype(dtype))
    toks = _tokens(40)
    got = _forward(model, params, toks)
    want = REF.logits(small, SEED, toks)
    scale = float(np.max(np.abs(want)))
    # f32: the same arithmetic in another order (1.8e-7 of a largest
    # logit of ~1 at these sizes); bf16: the program's activations
    # rounded to 8 bits through two layers (0.5 % seen).
    tol = 1e-5 if dtype == "float32" else 0.02
    assert float(np.max(np.abs(got - want))) <= tol * scale


def test_prefill_then_decode_match_the_reference_forward(small,
                                                          monkeypatch):
    from repro.launch import serve
    model, params = _program(small)
    rows = {}
    real = serve.ContinuousServer._pick

    def keep(self, row_logits, uid, index):
        rows[(uid, index)] = np.asarray(row_logits, np.float32)
        return real(self, row_logits, uid, index)

    monkeypatch.setattr(serve.ContinuousServer, "_pick", keep)
    srv = serve.ContinuousServer(model, num_slots=2, capacity=128,
                                 page_size=16, quant="none")
    reqs = [{"uid": u, "prompt": _tokens(n, u), "max_new": m}
            for u, (n, m) in enumerate([(16, 6), (80, 9), (33, 4)])]
    out = srv.generate(params, reqs)
    for r in reqs:
        seq = np.concatenate([r["prompt"], out[r["uid"]][:-1]])
        want = REF.logits(small, SEED, seq)
        n = len(r["prompt"])
        for i in range(r["max_new"]):
            got = rows[(r["uid"], i)]
            ref = want[n - 1 + i]
            # bf16 serving against the f32 forward: as the forward
            # test, plus the bf16 token-shift rows the state keeps.
            assert np.max(np.abs(got - ref)) <= 0.02 * np.max(
                np.abs(ref)), (r["uid"], i)


# Each program leaf from the HF tensors, written out by hand: torch
# stores a Linear (out, in), the program multiplies x @ W.
def _expected_leaf(key, hf):
    a, f = "attention.", "feed_forward."
    per_layer = {
        "pre_norm/scale": "ln1.weight", "pre_norm/bias": "ln1.bias",
        "mlp_norm/scale": "ln2.weight", "mlp_norm/bias": "ln2.bias",
        "attn/maa_x": a + "time_maa_x", "attn/decay_base": a + "time_decay",
        "attn/maa_w1": a + "time_maa_w1", "attn/maa_w2": a + "time_maa_w2",
        "attn/decay_w1": a + "time_decay_w1",
        "attn/decay_w2": a + "time_decay_w2",
        "attn/bonus": a + "time_faaaa",
        "attn/ln_x_scale": a + "ln_x.weight",
        "attn/ln_x_bias": a + "ln_x.bias",
        "mlp/maa_k": f + "time_maa_k", "mlp/maa_r": f + "time_maa_r"}
    linears = {"attn/wr": a + "receptance.weight", "attn/wk": a + "key.weight",
               "attn/wv": a + "value.weight", "attn/wg": a + "gate.weight",
               "attn/wo": a + "output.weight", "mlp/wk": f + "key.weight",
               "mlp/wv": f + "value.weight",
               "mlp/wr": f + "receptance.weight"}
    if key in per_layer:
        t = hf[per_layer[key]]
        return t.reshape(t.shape[-1]) if t.ndim == 3 and t.shape[0] == 1 \
            else t
    if key in linears:
        return hf[linears[key]].T
    if key == "attn/maa_base":
        return np.stack([hf[a + f"time_maa_{c}"].reshape(-1)
                         for c in "wkvrg"])
    raise KeyError(key)


def _layout_mismatches(cfg, params):
    words = np.asarray(DRIVER.W.seed_words(SEED))
    s = DRIVER.C.dims(cfg)
    bad = []
    stack = params["stacks"]["S0"]["L0"]
    flat = {"/".join(str(getattr(p, "key", p)) for p in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                stack)[0]}
    for layer in range(s["layers"]):
        hf = {n: np.asarray(t, np.float32) for n, t in DRIVER.C.tensors(
            words, tuple(DRIVER.C.layer_shapes(cfg).items()), layer,
            s).items()}
        for key, leaf in flat.items():
            if not np.array_equal(np.asarray(leaf[layer], np.float32),
                                  _expected_leaf(key, hf)):
                bad.append((layer, key))
    g = {n: np.asarray(t, np.float32) for n, t in DRIVER.C.tensors(
        words, tuple(DRIVER.C.global_shapes(cfg).items()), 0, s).items()}
    top = {"embed": g["embeddings.weight"],
           "lm_head": g["head.weight"].T,
           "ln0": g["blocks.0.pre_ln.weight"],
           "ln0_bias": g["blocks.0.pre_ln.bias"],
           "ln_out": g["ln_out.weight"], "ln_out_bias": g["ln_out.bias"]}
    got = {"embed": params["embed"]["table"], "lm_head": params["lm_head"],
           "ln0": params["embed_norm"]["scale"],
           "ln0_bias": params["embed_norm"]["bias"],
           "ln_out": params["final_norm"]["scale"],
           "ln_out_bias": params["final_norm"]["bias"]}
    bad += [k for k in top if not np.array_equal(
        np.asarray(got[k], np.float32), top[k])]
    return bad


def test_load_params_follow_the_hf_layout(small):
    model, params = _program(small)
    assert _layout_mismatches(small, params) == []


@pytest.mark.parametrize("fault", ["transposed", "swapped", "streams"])
def test_a_transposed_or_swapped_tensor_is_caught(small, fault):
    model, params = _program(small)
    attn = params["stacks"]["S0"]["L0"]["attn"]
    if fault == "transposed":      # a square matrix read (in, out)
        attn = dict(attn, wr=jnp.swapaxes(attn["wr"], 1, 2))
    elif fault == "swapped":       # key and value exchanged
        attn = dict(attn, wk=attn["wv"], wv=attn["wk"])
    else:                          # the w and r mix streams exchanged
        attn = dict(attn, maa_base=attn["maa_base"][:, [3, 1, 2, 0, 4]])
    bad = {**params, "stacks": {"S0": {"L0": dict(
        params["stacks"]["S0"]["L0"], attn=attn)}}}
    assert _layout_mismatches(small, bad)
    # The forward moves away from the reference by several times the
    # bf16 program's own distance (the streams' swap, the least
    # visible, by 6x).
    toks = _tokens(24)
    want = REF.logits(small, SEED, toks)
    own = np.max(np.abs(_forward(model, params, toks) - want))
    assert np.max(np.abs(_forward(model, bad, toks) - want)) > 3 * own


def _spec():
    spec = run.load_spec()
    spec["per_layer"] = []
    return spec


def test_the_cell_runs_correct_and_an_altered_token_is_not(small,
                                                           monkeypatch):
    out = run.run_cell(_spec(), "rwkv6-7b.prefill-long", SEED, 3.0, False,
                       require_chip=False, load_config=lambda f: small,
                       load_mix=lambda n: MIX)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"prompt_tok_s", "setup_s"}
    assert set(out["checks"]) == {"max_logit_gap", "wkv_state_err"}
    from repro.launch import serve
    real = serve.ContinuousServer._pick

    def altered(self, row_logits, uid, index):
        tok = real(self, row_logits, uid, index)
        return (tok + 1) % self.cfg.vocab_size if index == 3 else tok

    monkeypatch.setattr(serve.ContinuousServer, "_pick", altered)
    out = run.run_cell(_spec(), "rwkv6-7b.prefill-long", SEED, 3.0, False,
                       require_chip=False, load_config=lambda f: small,
                       load_mix=lambda n: MIX)
    assert not out["correct"] and out["failed"] > 0


def test_controls_read_above_the_limit_and_the_program_below(small):
    r = DRIVER.readings(small, MIX, SEED, 2.0, REF)
    assert r["answers"] >= 50
    assert r["program"]["max_logit_gap"] <= SMALL_GAP_LIMIT \
        < r["control"]["max_logit_gap"]
    assert r["program"]["wkv_state_err"] <= SMALL_STATE_LIMIT \
        < r["control"]["wkv_state_err"]
    assert "max_logit_gap" in r["control_bf16_state"]


# Prompts of 512 tokens, long enough for a bf16 state to drift (its
# rounding swallows the small updates of the slowly decaying channels).
LONG_MIX = dict(MIX, capacity=576, prompt_buckets=[512],
                prompt_weights=[1.0],
                check={"tokens": 30, "max_requests": 2})
_LONG: dict = {}


def _long_window(cfg):
    """One window of LONG_MIX and the program's admitted states,
    made once for the tests that judge them."""
    if not _LONG:
        state = DRIVER.setup(cfg, LONG_MIX, SEED)
        rec = DRIVER.window(state, 1.0)
        _LONG.update(rec=rec, held=DRIVER.release(state))
    return _LONG["rec"], _LONG["held"]


@pytest.mark.parametrize("control", [None, "bf16_state", "fp8"])
def test_a_lower_precision_state_is_not_correct(small, control):
    rec, held = _long_window(small)
    uids = DRIVER.sample(rec, LONG_MIX, SEED)
    assert uids and set(held) == set(uids)
    if control is not None:
        # The control's state after each prompt in the program's place.
        _, states = REF.served_gaps(small, SEED, DRIVER._seqs(rec, uids),
                                    control=control, states=True)
        held = dict(zip(uids, states))
    checks, failed = DRIVER.check(rec, small, LONG_MIX, SEED, REF, held)
    err = checks["wkv_state_err"][0]
    if control is None:
        assert failed == 0 and err <= SMALL_STATE_LIMIT
    else:
        assert failed == len(uids) and err > SMALL_STATE_LIMIT
    # The logits alone cannot tell a bf16 state from the program's.
    if control == "bf16_state":
        assert checks["max_logit_gap"][0] <= SMALL_GAP_LIMIT


def test_a_request_without_the_program_state_fails(small):
    rec, held = _long_window(small)
    uids = DRIVER.sample(rec, LONG_MIX, SEED)
    _, failed = DRIVER.check(rec, small, LONG_MIX, SEED, REF,
                             {u: held[u] for u in uids[1:]})
    assert failed == 1


def test_the_admitted_state_is_the_reference_state_in_f32(small):
    # The program in f32 leaves in its store the state the reference
    # holds after the same prompt: layer by layer, slot 0, in the
    # reference's (head, key dim, value dim) order.
    from repro.launch import serve
    model, params = _program(small, jnp.float32)
    srv = serve.ContinuousServer(model, num_slots=2, capacity=128,
                                 page_size=16, quant="none")
    toks = _tokens(70, 3)
    with jax.default_matmul_precision("highest"):
        got = DRIVER.admitted_state(srv, params, toks)
    _, want = REF.served_gaps(small, SEED, [(np.append(toks, 1), 70)],
                              states=True)
    assert got.shape == (2, 4, 64, 64)
    # The same f32 arithmetic in another order (2e-6 seen).
    assert DRIVER.state_err(got, want[0]) <= 1e-4
    swapped = np.swapaxes(got, -1, -2)
    assert DRIVER.state_err(swapped, want[0]) > 0.5


def test_state_err_is_the_first_layer_relative_distance():
    want = np.ones((3, 2, 4, 4))
    got = want.copy()
    got[0] *= 1.01
    got[1] *= 1.02
    got[2, 0] = 0.0
    assert DRIVER.layer_errs(got, want) == pytest.approx(
        [0.01, 0.02, np.sqrt(0.5)])
    assert DRIVER.state_err(got, want) == pytest.approx(0.01)


def test_the_record_carries_the_wkv_ops_and_the_window_state_bytes(small):
    state = DRIVER.setup(small, MIX, SEED)
    assert state.wkv_ops and all(isinstance(n, str)
                                 for n in state.wkv_ops)
    rec = DRIVER.window(state, 1.0)
    steps = len(DRIVER.decode_steps(rec))
    # Every decode step copies each live slot's state: the whole
    # (layers, slots, ...) leaves, 2 x 4 x (4 x 64 x 64 x 4 + 2 x 256
    # x 2) bytes a slot; admissions copy them once more.
    per_copy = 2 * 4 * (4 * 64 * 64 * 4 + 2 * 256 * 2)
    assert rec.state_bytes >= steps * per_copy
    assert rec.state_bytes % per_copy == 0
    work = DRIVER.work(rec)
    assert work["prompt_tokens"] > 0
    assert work["state_bytes"] == rec.state_bytes
    DRIVER.release(state)


def test_finch_counts_at_the_published_widths():
    with open(os.path.join(run.BENCH, "configs", "rwkv6-7b.json")) as f:
        fin = flops_rwkv6.Finch(json.load(f))
    assert fin.layer_matmul_params() == 221_773_824
    assert fin.layer_params() == 221_839_360
    # 16 layers, embedding, head, ln0 and ln_out: 4.09 B, 8.17 GB.
    assert fin.weight_bytes() == 2 * (16 * 221_839_360
                                      + 2 * 65536 * 4096 + 4 * 4096)
    assert fin.weight_bytes() / 1e9 == pytest.approx(8.17, abs=0.01)
    assert fin.prefill_flops(1) - 2 * fin.head_params() \
        - fin.wkv_flops(1) == pytest.approx(7.097e9, rel=1e-3)
    # The WKV: 80 KiB a token and layer in and out, 1 MiB of state a
    # layer; an 8k prompt at 819 GB/s needs 13.1 ms.
    assert fin.wkv_bytes(1) == 16 * (5 * 4096 * 4 + 64 * 64 * 64 * 4)
    assert fin.wkv_bytes(8192) / 819e9 * 1e3 == pytest.approx(13.13,
                                                              abs=0.01)
    assert fin.wkv_flops(1) == 16 * 64 * (5 * 64 * 64 + 5 * 64)
    assert fin.state_bytes() == 16 * (64 * 64 * 64 * 4 + 2 * 4096 * 2)
    assert fin.decode_step_bytes([5, 900]) - fin.decode_step_bytes([]) \
        == 4 * fin.state_bytes()


# ------------------------------------------------------------- readers

PLANE = "/device:TPU:0"


def _reader(name):
    return _module("metrics", f"{name}.py", name="t_rd_" +
                   name.replace(".", "_"))


def _ctx():
    """Three admissions (host spans 0-11, 19-41 and 69-91 ms, prompts
    of 3,000, 1,000 and 2,000 tokens) with their prefills (0-10, 20-40,
    70-90 ms) and a decode (50-60 ms).  The WKV's loop ``while.3`` runs
    2-6 ms and 22-34 ms, its body's ``fusion.7`` inside it (3-5 ms
    counts once with the loop); the decode program has an op of the
    same name, which is not counted; ``fusion.9`` is not the WKV's.
    The profiler kept no op past 59 ms, so the third prefill, which the
    trace does not hold whole, is left out."""
    ms = 1e6
    ev = [T.Event(PLANE, T.MODULES_LINE, "jit_prefill(1)", 0, 10 * ms),
          T.Event(PLANE, T.MODULES_LINE, "jit_prefill(2)", 20 * ms, 40 * ms),
          T.Event(PLANE, T.MODULES_LINE, "jit_decode(3)", 50 * ms, 60 * ms),
          T.Event(PLANE, T.MODULES_LINE, "jit_prefill(1)", 70 * ms, 90 * ms)]
    for s, e, n in ((2, 6, "while.3"), (3, 5, "fusion.7"),
                    (6, 9, "fusion.9"), (22, 34, "while.3"),
                    (33, 36, "fusion.7"), (51, 59, "while.3")):
        ev.append(T.Event(PLANE, T.OPS_LINE, f"%{n} = f32[] fusion()",
                          s * ms, e * ms))
    for s, e in ((0, 11), (19, 41), (69, 91)):
        ev.append(T.Event("bench", "spans", "bench.admit", s * ms, e * ms))
    ev.append(T.Event("bench", "spans", "bench.decode", 41 * ms, 61 * ms))
    with open(os.path.join(run.BENCH, "configs", "rwkv6-7b.json")) as f:
        fin = flops_rwkv6.Finch(json.load(f))
    rec = DRIVER.Record(
        t_open=0.0, t_close=0.1, tokens={1: [1, 2], 2: [3], 3: [4]},
        times={}, prompts={1: [0] * 3000, 2: [0] * 1000, 3: [0] * 2000},
        spans=[], dec=fin, events=[(1, 0), (2, 0), (1, 1), (3, 0)],
        served={}, finished=set(),
        wkv_ops=frozenset({"while.3", "fusion.7"}), state_bytes=6e6)
    work = {"prompt_tokens": 6000, "flops": 197e12 * 0.01,
            "decode_steps": 3, "state_bytes": 6e6}
    tr = {"events": ev, "plane": PLANE, "planes": [PLANE], "lo": 0.0,
          "hi": 100 * ms, "busy_s": 0.05, "window_s": 0.1}
    return {"record": rec, "trace": tr, "work": work, "compiles": [],
            "peaks": peaks.peak("TPU v5 lite")}


def test_wkv_time_is_read_from_the_prefills_the_trace_holds_whole():
    # 2-6 ms for the 3,000-token prompt, 22-36 ms for the 1,000.
    got = flops_rwkv6.wkv_prefills(_ctx())
    assert [n for n, _ in got] == [3000, 1000]
    assert [s for _, s in got] == pytest.approx([0.004, 0.014])


def test_the_rwkv_readers_on_a_fixture_trace():
    ctx = _ctx()
    assert _reader("rwkv.wkv_ms_per_ktok").read(ctx) == pytest.approx(4.5)
    fin = ctx["record"].dec
    least = (fin.wkv_bytes(3000) + fin.wkv_bytes(1000)) / 819e9
    assert least > (fin.wkv_flops(3000) + fin.wkv_flops(1000)) / 197e12
    assert _reader("rwkv.wkv_roofline").read(ctx) == pytest.approx(
        100 * least / 0.018)
    # 0.01 s of peak FLOPs over a 0.1 s window, with the Finch counts.
    assert _reader("prefill.mfu_pct").read(ctx) == pytest.approx(10.0)
    assert _reader("rwkv.state_copy_mb_per_step").read(ctx) == \
        pytest.approx(2.0)


@pytest.mark.parametrize("name", ["rwkv.wkv_ms_per_ktok",
                                  "rwkv.wkv_roofline",
                                  "rwkv.state_copy_mb_per_step"])
def test_the_rwkv_readers_read_nothing_without_their_inputs(name):
    reader = _reader(name)
    ctx = _ctx()
    assert reader.read(dict(ctx, trace=None)) is None
    # A record without the WKV's ops (another serving driver's) and
    # work without the Finch counts: nothing to read.
    glm = dict(ctx, work={"prompt_tokens": 6000, "flops": 1e12,
                          "decode_steps": 3})
    glm["record"] = None
    assert reader.read(glm) is None


@pytest.mark.parametrize("name", ["rwkv.wkv_ms_per_ktok",
                                  "rwkv.wkv_roofline"])
def test_the_wkv_readers_read_nothing_when_no_op_name_matches(name):
    ctx = _ctx()
    ctx["record"] = dataclasses.replace(
        ctx["record"], wkv_ops=frozenset({"while.99", "fusion.98"}))
    assert _reader(name).read(ctx) is None
