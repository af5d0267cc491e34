"""Operations and bytes the algorithms need, computed from shapes.

The rooflines and MFU divide by these.  They count the work the
algorithm needs, whatever engine implements it: a reduction reads each
input element once; a decoder multiplies each weight once per token,
attends over the context it has (the causal half in a prefill), and
takes logits where the program needs them.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def reduce_bytes(n: int, dtype: str = "float32") -> int:
    """Bytes one reduction of ``n`` elements has to read."""
    return int(n) * jnp.dtype(dtype).itemsize


def reduce_flops(n: int) -> int:
    """One add (``reduce_sum``) or one fused multiply-add
    (``squared_sum``) per element."""
    return int(n)


class Decoder:
    """A dense GQA decoder's counts, from its configuration file's
    sizes (the keys of THUDM/glm-4-9b's ``config.json``)."""

    def __init__(self, cfg: dict):
        self.d = int(cfg["hidden_size"])
        self.heads = int(cfg["num_attention_heads"])
        self.kv = int(cfg["multi_query_group_num"])
        self.hd = int(cfg["kv_channels"])
        self.ff = int(cfg["ffn_hidden_size"])
        self.vocab = int(cfg["padded_vocab_size"])
        self.layers = int(cfg["num_layers"])
        self.qkv_bias = bool(cfg["add_qkv_bias"])

    def layer_matmul_params(self) -> int:
        d, q, kv = self.d, self.heads * self.hd, self.kv * self.hd
        return d * q + 2 * d * kv + q * d + 3 * d * self.ff

    def layer_params(self) -> int:
        """Every parameter of one layer: projections, QKV biases and
        the two norms' weights."""
        bias = self.heads * self.hd + 2 * self.kv * self.hd \
            if self.qkv_bias else 0
        return self.layer_matmul_params() + bias + 2 * self.d

    def embed_params(self) -> int:
        return self.vocab * self.d

    def head_params(self) -> int:
        return self.d * self.vocab

    def weight_bytes(self, itemsize: int = 2) -> int:
        return itemsize * (self.layers * self.layer_params()
                           + self.embed_params() + self.head_params()
                           + self.d)

    def kv_bytes_per_token(self, itemsize: int = 2) -> int:
        """K and V of one position, over every layer."""
        return 2 * self.kv * self.hd * self.layers * itemsize

    def decode_step_bytes(self, contexts, itemsize: int = 2) -> int:
        """Bytes one batched decode step has to read: every layer's
        weights and the output layer's once, and the K and V of each
        live slot's earlier positions (``contexts``, one per slot).
        The embedding row and the slots' dead capacity are not
        needed."""
        keys = int(np.sum(np.asarray(contexts, np.int64)))
        return (itemsize * (self.layers * self.layer_params()
                            + self.head_params())
                + self.kv_bytes_per_token(itemsize) * keys)

    def attention_flops(self, query_positions) -> int:
        """Scores and the weighted sum of values: 4 * heads * head_dim
        multiply-adds per (query, key) pair, per layer; a query at
        position p sees p + 1 keys."""
        keys = int(np.sum(np.asarray(query_positions, np.int64) + 1))
        return 4 * self.heads * self.hd * keys * self.layers

    def decode_flops(self, context: int) -> int:
        """One token at position ``context`` (``context`` earlier
        tokens): every weight once, attention, logits."""
        return (2 * (self.layers * self.layer_matmul_params()
                     + self.head_params())
                + self.attention_flops([context]))

    def prefill_flops(self, prompt_len: int) -> int:
        """A prompt of ``prompt_len`` tokens: every weight once per
        token, causal attention, logits at the last position only."""
        s = int(prompt_len)
        return (2 * self.layers * self.layer_matmul_params() * s
                + self.attention_flops(np.arange(s))
                + 2 * self.head_params())


def mfu_pct(ctx) -> float | None:
    """Whole-step model FLOP utilisation of a traced window: the FLOPs
    its tokens needed, over the window, over the chip's bf16 peak."""
    work, tr = ctx["work"], ctx["trace"]
    if not tr or not work.get("flops"):
        return None
    return 100.0 * work["flops"] / tr["window_s"] / ctx["peaks"]["bf16_flops"]
