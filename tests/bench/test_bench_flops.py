"""FLOP and byte counts (bench/flops.py) against hand counts for
glm4-9b and the f32 reduction."""

import json
import os

import pytest

from bench import flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def dec():
    with open(os.path.join(ROOT, "bench", "configs", "glm4-9b.json")) as f:
        return flops.Decoder(json.load(f))


def test_glm4_parameter_counts(dec):
    assert dec.layer_params() == 203_960_832
    assert dec.embed_params() == 620_756_992
    assert dec.head_params() == 620_756_992
    assert dec.layers == 20
    # 20 layers + embedding + head + final norm, two bytes each.
    assert dec.weight_bytes() == 2 * (20 * 203_960_832
                                      + 2 * 620_756_992 + 4096)
    assert dec.weight_bytes() / 1e9 == pytest.approx(10.64, abs=0.01)


def test_glm4_decode_flops_per_token(dec):
    no_attention = dec.decode_flops(0) - dec.attention_flops([0])
    assert no_attention == pytest.approx(
        2 * (20 * 203.96e6 + 620.76e6), rel=1e-4)
    assert no_attention / 1e9 == pytest.approx(9.40, abs=0.01)
    # Attention grows with the context: 4 * 32 * 128 per key per layer.
    assert dec.decode_flops(1000) - dec.decode_flops(999) == \
        4 * 32 * 128 * 20


def test_glm4_prefill_flops_take_logits_at_the_last_position(dec):
    s = 2048
    body = dec.prefill_flops(s) - dec.attention_flops(range(s)) \
        - 2 * dec.head_params()
    assert body / s / 1e9 == pytest.approx(8.16, abs=0.01)
    # Causal: position p sees p + 1 keys.
    assert dec.attention_flops(range(s)) == \
        4 * 32 * 128 * 20 * s * (s + 1) // 2


def test_decode_step_reads_weights_once_and_each_slots_context():
    cfg = {"hidden_size": 8, "num_attention_heads": 2,
           "multi_query_group_num": 1, "kv_channels": 4,
           "ffn_hidden_size": 16, "padded_vocab_size": 32,
           "num_layers": 3, "add_qkv_bias": True}
    small = flops.Decoder(cfg)
    # A layer: q 8x8, k and v 8x4 each, o 8x8, gate/up/down 3 x 8x16,
    # biases 8 + 4 + 4, two norms of 8.
    layer = 64 + 2 * 32 + 64 + 3 * 128 + 16 + 16
    assert small.layer_params() == layer
    weights = 2 * (3 * layer + 8 * 32)      # bf16, layers + output layer
    kv = 2 * 1 * 4 * 3 * 2                  # K and V, 3 layers, bf16
    assert small.kv_bytes_per_token() == kv == 48
    assert small.decode_step_bytes([]) == weights
    assert small.decode_step_bytes([5, 0, 11]) == weights + 16 * kv


def test_glm4_decode_step_bytes(dec):
    # 9.40 GB of weights, 20,480 bytes of K and V a position.
    assert dec.decode_step_bytes([0]) / 1e9 == pytest.approx(9.40,
                                                              abs=0.01)
    assert dec.decode_step_bytes([100] * 16) - dec.decode_step_bytes([]) \
        == 1600 * 20480


def test_decode_steps_split_at_admissions_and_repeats():
    from bench import run
    serve = run.load_module(os.path.join(run.BENCH, "drivers",
                                         "serve.py"), "t_drv_steps")
    rec = serve.Record(
        t_open=0.0, t_close=1.0, tokens={}, times={},
        prompts={1: [0] * 10, 2: [0] * 20, 3: [0] * 5}, spans=[],
        dec=None, served={}, finished=set(),
        # admit 1, 2; step; step; admit 3; step; step (2 finished)
        events=[(1, 0), (2, 0), (1, 1), (2, 1), (1, 2), (2, 2),
                (3, 0), (1, 3), (2, 3), (3, 1), (1, 4), (3, 2)])
    assert serve.decode_steps(rec) == [[10, 20], [11, 21],
                                       [12, 22, 5], [13, 6]]


def test_reduction_reads_each_element_once():
    assert flops.reduce_bytes(1 << 28) == 1 << 30
    assert flops.reduce_bytes(10, "bfloat16") == 20
    assert flops.reduce_flops(1 << 20) == 1 << 20
