"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology it is told
about, and refuses what the chip would refuse (blocks not aligned to
the (8, 128) tiling, scalar stores to VMEM, more VMEM than a kernel may
use).  Each case compiles one kernel at the size its callers use on
the chip, with ``interpret=False``, and checks that the compiled
program holds the kernel (a ``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.mma_attention import mma_attention
from repro.kernels.mma_norm_matmul import mma_norm_matmul

N = 1 << 24
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _attn_decode(q, k, v, qpos, kv_len):
    return mma_attention(q, k, v, qpos=qpos, kv_len=kv_len, cap=50.0,
                         interpret=False)


def _attn_prefill(window):
    def run(q, k, v):
        return mma_attention(q, k, v, qpos=jnp.arange(q.shape[1]),
                             causal=True, window=window, cap=50.0,
                             interpret=False)
    return run


# name -> (function, argument (shape, dtype) list)
CASES = {
    "reduce_single_pass": (
        lambda x: ops.mma_reduce(x, interpret=False), [((N,), jnp.float32)]),
    "reduce_split": (
        lambda x: ops.mma_reduce(x, variant="split", interpret=False),
        [((N,), jnp.float32)]),
    "reduce_recurrence": (
        lambda x: ops.mma_reduce(x, variant="recurrence", interpret=False),
        [((N,), jnp.float32)]),
    "reduce_partials": (
        lambda x: ops.mma_reduce_partials(x, interpret=False),
        [((N,), jnp.float32)]),
    "squared_sum": (
        lambda x: ops.mma_squared_sum(x, interpret=False),
        [((N,), jnp.float32)]),
    "ec_reduce_w2": (
        lambda x: ops.mma_ec_reduce(x, split_words=2, interpret=False),
        [((N,), jnp.float32)]),
    "ec_reduce_w3": (
        lambda x: ops.mma_ec_reduce(x, split_words=3, interpret=False),
        [((N,), jnp.float32)]),
    "ec_squared_sum_w2": (
        lambda x: ops.mma_ec_squared_sum(x, split_words=2,
                                         interpret=False),
        [((N,), jnp.float32)]),
    "ec_squared_sum_w3": (
        lambda x: ops.mma_ec_squared_sum(x, split_words=3,
                                         interpret=False),
        [((N,), jnp.float32)]),
    "dd_reduce": (
        lambda x: ops.mma_dd_reduce(x, interpret=False),
        [((N,), jnp.float32)]),
    "dd_squared_sum": (
        lambda x: ops.mma_dd_squared_sum(x, interpret=False),
        [((N,), jnp.float32)]),
    "scan_inclusive": (
        lambda x: ops.mma_scan(x, interpret=False), [((N,), jnp.float32)]),
    "scan_exclusive": (
        lambda x: ops.mma_scan(x, inclusive=False, interpret=False),
        [((N,), jnp.float32)]),
    "segment_sum_64": (
        lambda x, i: ops.mma_segment_sum(x, i, 64, interpret=False),
        [((N,), jnp.float32), ((N,), jnp.int32)]),
    "attention_decode_b8_sk4096": (
        _attn_decode,
        [((8, 1, 4, 2, 256), BF16), ((8, 4096, 4, 256), BF16),
         ((8, 4096, 4, 256), BF16), ((8, 1), jnp.int32),
         ((8,), jnp.int32)]),
    "attention_prefill_sq2048": (
        _attn_prefill(None),
        [((1, 2048, 4, 2, 256), BF16), ((1, 2048, 4, 256), BF16),
         ((1, 2048, 4, 256), BF16)]),
    "attention_prefill_sq32k_window4096": (
        _attn_prefill(4096),
        [((1, 32768, 4, 2, 256), BF16), ((1, 32768, 4, 256), BF16),
         ((1, 32768, 4, 256), BF16)]),
    "rmsnorm_d2304": (
        lambda x, w: ops.mma_rmsnorm(x, w, interpret=False),
        [((512, 2304), BF16), ((2304,), jnp.float32)]),
    "norm_matmul_2304x9216": (
        lambda x, s, w: mma_norm_matmul(x, s, w, interpret=False),
        [((512, 2304), BF16), ((2304,), jnp.float32),
         ((2304, 9216), BF16)]),
    "norm_matmul_gated_2304x9216": (
        lambda x, s, w, g: mma_norm_matmul(x, s, w, w_gate=g, act="gelu",
                                           interpret=False),
        [((512, 2304), BF16), ((2304,), jnp.float32),
         ((2304, 9216), BF16), ((2304, 9216), BF16)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, arg_specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in arg_specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
