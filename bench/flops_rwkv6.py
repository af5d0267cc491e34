"""Operations and bytes RWKV-6 "Finch" needs, computed from shapes,
and the device time of its WKV recurrence in a traced window.

The model step's counts have the methods the serving driver's ``work``
calls (``prefill_flops``, ``decode_flops``, ``decode_step_bytes``):
every weight multiplied once per token, the WKV recurrence, logits
where the program needs them.  The WKV's own least work, whatever
implements it: per token and layer it reads r, k, v and w and writes y
in f32 (5 d values), and does 5 hs^2 + 5 hs operations a head (r S,
the bonus term, the decayed update); its 64 x 64 f32 state per head is
written once per sequence (a prefill starts from zero).
"""

from __future__ import annotations

import numpy as np

from bench import trace as T

F32 = 4
# Host and device timelines of a trace sit apart by up to ~2 ms.
SLACK_NS = 2e6


class Finch:
    """Counts of a Finch stage, from its configuration file's sizes
    (the keys of RWKV/v6-Finch-7B-HF's ``config.json`` and the two
    LoRA widths)."""

    def __init__(self, cfg: dict):
        self.d = int(cfg["hidden_size"])
        self.hs = int(cfg["head_size"])
        self.heads = self.d // self.hs
        self.ff = int(cfg["intermediate_size"])
        self.vocab = int(cfg["vocab_size"])
        self.layers = int(cfg["num_hidden_layers"])
        self.mix = int(cfg["time_mix_extra_dim"])
        self.decay = int(cfg["time_decay_extra_dim"])

    def layer_matmul_params(self) -> int:
        d, m, R = self.d, self.mix, self.decay
        time_mix = 5 * d * d + 2 * 5 * m * d + 2 * R * d
        return time_mix + 2 * d * self.ff + d * d

    def layer_params(self) -> int:
        """Every parameter of one layer: the matrices, six time-mix and
        two channel-mix shift vectors, decay, bonus, ``ln_x`` and the
        two LayerNorms (weight and bias each)."""
        return self.layer_matmul_params() + 16 * self.d

    def embed_params(self) -> int:
        return self.vocab * self.d

    def head_params(self) -> int:
        return self.d * self.vocab

    def weight_bytes(self, itemsize: int = 2) -> int:
        """The stage in bf16: its layers, the embedding and ``ln0``,
        ``ln_out`` and the head."""
        return itemsize * (self.layers * self.layer_params()
                           + self.embed_params() + self.head_params()
                           + 4 * self.d)

    def state_bytes(self) -> int:
        """One sequence's recurrent state over the stage: the f32 WKV
        state and the two bf16 token-shift rows of every layer."""
        return self.layers * (self.heads * self.hs * self.hs * F32
                              + 2 * 2 * self.d)

    # -------------------------------------------------------- the WKV

    def wkv_flops(self, tokens: int) -> int:
        h = self.hs
        return self.layers * self.heads * (5 * h * h + 5 * h) * int(tokens)

    def wkv_bytes(self, tokens: int) -> int:
        """r, k, v, w in and y out in f32 per token and layer, and the
        final state once per sequence."""
        return self.layers * (5 * self.d * F32 * int(tokens)
                              + self.heads * self.hs * self.hs * F32)

    # ---------------------------------------------------- model steps

    def decode_flops(self, context: int) -> int:
        """One token (any context: the state does not grow): every
        weight once, the WKV, logits."""
        return (2 * (self.layers * self.layer_matmul_params()
                     + self.head_params()) + self.wkv_flops(1))

    def prefill_flops(self, prompt_len: int) -> int:
        """A prompt: every weight once per token, the WKV over every
        token, logits at the last position only."""
        s = int(prompt_len)
        return (2 * self.layers * self.layer_matmul_params() * s
                + self.wkv_flops(s) + 2 * self.head_params())

    def decode_step_bytes(self, contexts, itemsize: int = 2) -> int:
        """Bytes one batched decode step has to move: the layers' and
        the head's weights read once, and each live slot's state read
        and written (``contexts``, one per slot; their lengths do not
        matter)."""
        return (itemsize * (self.layers * self.layer_params()
                            + self.head_params())
                + 2 * self.state_bytes() * len(list(contexts)))


def wkv_prefills(ctx) -> list:
    """``[(prompt tokens, WKV device seconds)]``: one pair for each
    admission of the traced window whose ``jit_prefill`` program the
    trace holds whole.

    The WKV's ops are the instructions ``serve_rwkv6.wkv_ops`` found
    under the ``rwkv.wkv`` scope of the compiled prefill
    (``record.wkv_ops``); its time in a program is the union of their
    intervals (a loop and the ops of its body are both on the trace).
    The scan puts ops on the trace for every token and layer, and the
    trace of a window may hold the ops of only a first stretch of it:
    a program counts where it lies inside the stretch the ops cover.  Each program is paired with the
    admission (span ``bench.admit`` of the serving window) around its
    middle, and through it with its prompt: the window's k-th
    admission span is its k-th token of index 0."""
    tr, rec = ctx.get("trace"), ctx.get("record")
    names = getattr(rec, "wkv_ops", None)
    if not tr or not names:
        return []
    ev, plane, lo, hi = tr["events"], tr["plane"], tr["lo"], tr["hi"]
    ops = sorted((e for e in ev if e.plane == plane
                  and e.line == T.OPS_LINE and lo <= e.start <= hi),
                 key=lambda e: e.start)
    admits = sorted((e for e in ev if e.name == "bench.admit"),
                    key=lambda e: e.start)
    lens = [len(rec.prompts[u]) for u, i in rec.events if i == 0]
    if not ops or len(admits) != len(lens):
        return []
    first = ops[0].start - SLACK_NS
    last = max(e.end for e in ops) + SLACK_NS
    starts = np.array([e.start for e in ops])
    out = []
    for m in T.programs(ev, plane, T.PROGRAMS["prefill"], lo, hi):
        if m.start < first or m.end > last:
            continue
        mid = (m.start + m.end) / 2
        k = [j for j, a in enumerate(admits) if a.start <= mid <= a.end]
        i0 = np.searchsorted(starts, m.start)
        i1 = np.searchsorted(starts, m.end, side="right")
        wkv = [(e.start, e.end) for e in ops[i0:i1]
               if e.end <= m.end and T.op_name(e.name) in names]
        if len(k) == 1 and wkv:
            out.append((lens[k[0]],
                        sum(b - a for a, b in T.union(wkv)) * 1e-9))
    return out
