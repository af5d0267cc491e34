"""Driver of the RWKV-6 "Finch" serving cells.

The system under test is ``repro.launch.serve.ContinuousServer`` on the
program's own model, ``model_zoo`` built from
``registry.get_config("rwkv6-7b")`` with only ``num_layers`` set to the
configuration's stage, served as the serving cells of glm4 are: the
same server arguments, window, end-to-end metrics, drain and sample
(imported from ``bench/drivers/serve.py``).  The weights are the
seeded HF-layout checkpoint of ``bench.checkpoint_rwkv6``, loaded into
the program's tree on the device in one jitted call, in the
checkpoint's bf16.

``check`` compares two numbers with the plain reference, over the
sample of finished requests that glm4's check draws: the widest logit
gap of a served token (``max_logit_gap``), and the widest relative
distance of the WKV state the program's store holds after admitting a
prompt from the reference's f32 state after it (``wkv_state_err``:
``|S - S_ref|_F / |S_ref|_F`` of the stage's first layer, the largest
over prompts; ``state_err``).  The logits barely see the state's precision (a reference
that rounds its state to bf16 after every token picks the program's
tokens); the state does.  ``release`` takes the program's states
before it frees the program: for each prompt of the sample it admits
the prompt as ``ContinuousServer.serve`` does (the server's own jitted
prefill, which the window ran, into a store of the server's own
layout through ``write_slot``) and reads the slot's dense ``wkv``
leaves back.

For the per-layer metrics the record carries two things more: the
names of the compiled prefill's HLO instructions whose ``op_name``
metadata holds the ``rwkv.wkv`` scope (the trace's op events name the
instruction, not its scope), and the bytes the paged store's eager
copies of dense per-slot state moved while the window was open
(counter ``store.state_bytes``).
"""

from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np

from bench import checkpoint_rwkv6 as C
from bench import flops_rwkv6, generator
from bench import weights as W
from bench.drivers import serve as S
from bench.drivers.serve import (attempted, decode_steps,  # noqa: F401
                                 end_to_end, sample)

ARCH = "rwkv6-7b"
WKV_SCOPE = "rwkv.wkv"
STATE_COUNTER = "store.state_bytes"


def model_config(cfg: dict):
    """The registry's ``rwkv6-7b`` with the stage's ``num_layers``; every
    width is checked against the configuration file."""
    from repro.configs import registry
    if cfg["torch_dtype"] != "bfloat16":
        raise ValueError(f"weights in {cfg['torch_dtype']}")
    mc = dataclasses.replace(registry.get_config(ARCH),
                             num_layers=int(cfg["num_hidden_layers"]))
    s = C.dims(cfg)
    got = {"d_model": mc.d_model, "head_size": mc.rwkv.head_size,
           "num_heads": mc.num_heads, "d_ff": mc.d_ff,
           "vocab_size": mc.vocab_size, "lora_mix": mc.rwkv.lora_mix,
           "lora_decay": mc.rwkv.lora_decay, "pattern": mc.pattern,
           "norm_type": mc.norm_type, "tie": mc.tie_embeddings}
    want = {"d_model": s["d"], "head_size": s["hs"],
            "num_heads": s["heads"], "d_ff": s["ff"],
            "vocab_size": s["vocab"], "lora_mix": s["mix"],
            "lora_decay": s["decay"], "pattern": ("rwkv",),
            "norm_type": "layernorm",
            "tie": bool(cfg["tie_word_embeddings"])}
    if got != want:
        diff = {k: (got[k], want[k]) for k in got if got[k] != want[k]}
        raise ValueError(f"the program's {ARCH} differs from the "
                         f"configuration (program, file): {diff}")
    return mc


def _sources(cfg: dict) -> tuple:
    """Where each leaf of the program's parameter tree comes from: the
    HF tensor and the flat HF-layout index of each program-layout
    coordinate.  The program multiplies ``x @ W`` where torch stores
    ``W`` as (out, in); ``maa_base`` stacks the five streams'
    ``time_maa_*`` (w, k, v, r, g) as its rows."""
    s = C.dims(cfg)
    d, ff, m, R, hs = s["d"], s["ff"], s["mix"], s["decay"], s["hs"]
    u = jnp.uint32
    a, f = "attention.", "feed_forward."

    def vec(name):
        return (name, lambda i: i)

    def linear(name, n_in):
        return (name, lambda i, o: o * u(n_in) + i)

    layer = {
        "pre_norm/scale": vec("ln1.weight"),
        "pre_norm/bias": vec("ln1.bias"),
        "mlp_norm/scale": vec("ln2.weight"),
        "mlp_norm/bias": vec("ln2.bias"),
        "attn/maa_x": vec(a + "time_maa_x"),
        "attn/maa_base": ([a + f"time_maa_{c}" for c in C.STREAMS],
                          lambda i: i),
        "attn/maa_w1": (a + "time_maa_w1", lambda i, c: i * u(5 * m) + c),
        "attn/maa_w2": (a + "time_maa_w2",
                        lambda g, r, o: (g * u(m) + r) * u(d) + o),
        "attn/decay_base": vec(a + "time_decay"),
        "attn/decay_w1": (a + "time_decay_w1", lambda i, r: i * u(R) + r),
        "attn/decay_w2": (a + "time_decay_w2", lambda r, o: r * u(d) + o),
        "attn/bonus": (a + "time_faaaa", lambda h, j: h * u(hs) + j),
        "attn/wr": linear(a + "receptance.weight", d),
        "attn/wk": linear(a + "key.weight", d),
        "attn/wv": linear(a + "value.weight", d),
        "attn/wg": linear(a + "gate.weight", d),
        "attn/wo": linear(a + "output.weight", d),
        "attn/ln_x_scale": vec(a + "ln_x.weight"),
        "attn/ln_x_bias": vec(a + "ln_x.bias"),
        "mlp/maa_k": vec(f + "time_maa_k"),
        "mlp/maa_r": vec(f + "time_maa_r"),
        "mlp/wk": linear(f + "key.weight", d),
        "mlp/wv": linear(f + "value.weight", ff),
        "mlp/wr": linear(f + "receptance.weight", d),
    }
    top = {
        "embed/table": ("embeddings.weight", lambda v, i: v * u(d) + i),
        "embed_norm/scale": vec("blocks.0.pre_ln.weight"),
        "embed_norm/bias": vec("blocks.0.pre_ln.bias"),
        "final_norm/scale": vec("ln_out.weight"),
        "final_norm/bias": vec("ln_out.bias"),
        "lm_head": ("head.weight", lambda i, v: v * u(d) + i),
    }
    return layer, top


def load_params(cfg: dict, model, seed: int):
    """The checkpoint in the program's parameter tree, made on the
    device in one jitted call from the seed, in bf16."""
    layer_src, top_src = _sources(cfg)
    s = C.dims(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    stacks = shapes["stacks"]
    if list(stacks) != ["S0"] or list(stacks["S0"]) != ["L0"]:
        raise ValueError("expected one scanned stack of one layer kind")
    prefix = "stacks/S0/L0/"

    def make(words):
        def leaf(path, sds):
            key = "/".join(str(getattr(p, "key", p)) for p in path)
            iotas = [jax.lax.broadcasted_iota(jnp.uint32, sds.shape, ax)
                     for ax in range(len(sds.shape))]
            if key.startswith(prefix):
                name, index = layer_src[key[len(prefix):]]
                layer, iotas = iotas[0], iotas[1:]
            else:
                name, index = top_src[key]
                layer = 0
            if isinstance(name, list):
                # One row per stream: row g of the leaf is tensor g.
                row, iotas = iotas[0], iotas[1:]
                parts = [C.draw(words, n, index(*iotas), layer, s)
                         for n in name]
                out = parts[0]
                for g, p in enumerate(parts[1:], 1):
                    out = jnp.where(row == g, p, out)
                return out.astype(C.STORED)
            return C.draw(words, name, index(*iotas), layer, s)

        return jax.tree_util.tree_map_with_path(leaf, shapes)

    return jax.jit(make)(W.seed_words(seed))


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*?"
                    r"op_name=\"([^\"]*)\"")


def scope_ops(hlo_text: str, scope: str = WKV_SCOPE) -> frozenset:
    """Names of the instructions of a compiled module's HLO text whose
    ``op_name`` metadata passes through ``scope``."""
    out = set()
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and scope in m.group(2).split("/"):
            out.add(m.group(1))
    return frozenset(out)


def wkv_ops(server, params, mix: dict) -> frozenset:
    """The WKV's instructions in the prefill program of every prompt
    bucket of the mix, as compiled for the window (the persistent
    cache returns the same executable)."""
    cap = int(mix["capacity"])
    names = set()
    for L in (int(b) for b in mix["prompt_buckets"]):
        tokens = jax.ShapeDtypeStruct((1, L), jnp.int32)
        compiled = server._prefill.lower(
            params, {"tokens": tokens}, extra_capacity=cap - L).compile()
        names |= scope_ops(compiled.as_text())
    return frozenset(names)


def _state_bytes() -> int:
    from repro import obs
    return sum(obs.counters().get(STATE_COUNTER, {}).values())


class _Counted:
    """The server, with the store's state-copy counter read as each
    token is handed over, so that the window's share can be told from
    the drain's."""

    def __init__(self, server):
        self.server = server
        self.start = 0
        self.marks: list = []

    def serve(self, params, queue):
        self.start, self.marks = _state_bytes(), []
        gen = self.server.serve(params, queue)
        try:
            for ev in gen:
                self.marks.append(_state_bytes())
                yield ev
        finally:
            gen.close()


@dataclasses.dataclass
class State(S.State):
    wkv_ops: frozenset = frozenset()
    record: object = None         # the window's Record, for release


@dataclasses.dataclass
class Record(S.Record):
    wkv_ops: frozenset = frozenset()
    state_bytes: int = 0          # store.state_bytes in the window


def setup(cfg: dict, mix: dict, seed: int) -> State:
    from repro.launch.serve import ContinuousServer
    from repro.models import model_zoo
    model = model_zoo.build(model_config(cfg))
    params = load_params(cfg, model, seed)
    server = ContinuousServer(
        model, num_slots=int(mix["slots"]),
        capacity=int(mix["capacity"]),
        page_size=int(mix["page_size"]), quant="none")
    vocab = int(cfg["vocab_size"])
    # Warm pass of the mix's own shapes: every prompt bucket's prefill,
    # the decode step on every slot, the store's eager ops.
    for _ in server.serve(params, generator.warm_requests(mix, seed,
                                                          vocab)):
        pass
    return State(cfg, mix, seed, flops_rwkv6.Finch(cfg),
                 _Counted(server), params,
                 generator.requests(mix, seed, vocab),
                 wkv_ops=wkv_ops(server, params, mix))


def window(state: State, seconds: float) -> Record:
    rec = S.window(state, seconds)
    counted = state.server
    moved = counted.marks[len(rec.events) - 1] - counted.start
    state.record = Record(**vars(rec), wkv_ops=state.wkv_ops,
                          state_bytes=moved)
    return state.record


def admitted_state(server, params, prompt) -> np.ndarray:
    """The WKV state (layers, heads, hs, hs) the server's store holds
    for a slot right after admitting ``prompt``, as ``serve`` admits
    it."""
    prompt = np.asarray(prompt, np.int32)
    _, caches = server._prefill(params, {"tokens": jnp.asarray(
        prompt[None])}, server.capacity - prompt.shape[0])
    store = server._new_store()
    store.alloc_slot(0)
    store.write_slot(0, caches)
    dense = store.as_dense()
    leaves = [leaf for path, leaf in
              jax.tree_util.tree_flatten_with_path(dense)[0]
              if getattr(path[-1], "key", None) == "wkv"]
    if len(leaves) != 1:
        raise ValueError(f"expected one stacked wkv leaf, found "
                         f"{len(leaves)}")
    # (layers, slots, heads, hs, hs): slot 0.
    return np.asarray(leaves[0][:, 0], np.float32)


def release(state: State) -> dict:
    """The program's WKV state after each prompt of the check's sample
    (``{uid: state}``), then free the program's state on the device."""
    rec, held = state.record, {}
    if rec is not None:
        server = state.server.server
        for uid in sample(rec, state.mix, state.seed):
            held[uid] = admitted_state(server, state.params,
                                       rec.prompts[uid])
    state.record = None
    S.release(state)
    return held


def layer_errs(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per layer ``|got - want|_F / |want|_F`` of two stacked states."""
    got = np.asarray(got, np.float64).reshape(len(want), -1)
    want = np.asarray(want, np.float64).reshape(len(want), -1)
    return np.linalg.norm(got - want, axis=1) / np.maximum(
        np.linalg.norm(want, axis=1), 1e-30)


def state_err(got: np.ndarray, want: np.ndarray) -> float:
    """``|got - want|_F / |want|_F`` of the stage's first layer.  Its
    inputs carry no drift from earlier bf16 layers, so what it reads is
    the precision of the state itself; deeper layers add the drift of
    the layers above them (0.004 to 0.008 over four layers at width
    1,024 on the CPU), which would hide a bf16 state (0.016 to 0.018 in
    every layer there)."""
    return float(layer_errs(got, want)[0])


def _seqs(rec, uids) -> list:
    return [(np.concatenate([rec.prompts[u],
                             np.asarray(rec.served[u], np.int32)]),
             len(rec.prompts[u])) for u in uids]


def check(rec: Record, cfg: dict, mix: dict, seed: int, ref,
          released=None) -> tuple:
    """The widest logit gap of a served token and the widest distance
    of an admitted prompt's WKV state from the reference's, over the
    sample; a request fails if either number of it is over its
    limit."""
    lim = cfg["limits"]
    gap_lim = float(lim["max_logit_gap"])
    state_lim = float(lim["wkv_state_err"])
    uids = sample(rec, mix, seed)
    if not uids:
        return {"finished_requests": (0, 1)}, 1
    gaps, want = ref.served_gaps(cfg, seed, _seqs(rec, uids),
                                 states=True)
    held = released or {}
    worst = [float(np.max(g)) for g in gaps]
    errs = [state_err(held[u], w) if u in held else float("nan")
            for u, w in zip(uids, want)]
    failed = sum(1 for g, e in zip(worst, errs)
                 if not (g <= gap_lim and e <= state_lim))
    return {"max_logit_gap": (max(worst), gap_lim),
            "wkv_state_err": (max(errs), state_lim)}, failed


def work(rec: Record) -> dict:
    """The serving driver's counts (``bench/drivers/serve.py``
    ``work``) over the Finch counts, and the state bytes the store
    copied in the window."""
    return dict(S.work(rec), state_bytes=rec.state_bytes)


def readings(cfg: dict, mix: dict, seed: int, seconds: float,
             ref) -> dict:
    """The numbers ``check`` compares, for the program and for the two
    controls of the plain reference (fp8 matmuls; a bf16 WKV state),
    which need no decode: at each position of the same prompts and
    served tokens each reads the gap of the token it puts first, and
    its state after each prompt is put in the program's place."""
    state = setup(cfg, mix, seed)
    rec = window(state, seconds)
    held = release(state)
    del state
    uids = sample(rec, mix, seed)
    if not uids:
        return {"answers": 0, "program": {}, "control": {}}
    seqs = _seqs(rec, uids)
    prog, want = ref.served_gaps(cfg, seed, seqs, states=True)
    ctrl, ctrl_s = ref.served_gaps(cfg, seed, seqs, control=True,
                                   states=True)
    bf16, bf16_s = ref.served_gaps(cfg, seed, seqs, control="bf16_state",
                                   states=True)

    def top(gaps, states):
        return {"max_logit_gap": max(float(g.max()) for g in gaps),
                "wkv_state_err": max(state_err(s, w)
                                     for s, w in zip(states, want))}

    def by_layer(states):
        return np.max([layer_errs(s, w) for s, w in zip(states, want)],
                      axis=0).round(5).tolist()

    mine = [held[u] for u in uids]
    return {"answers": int(sum(len(g) for g in prog)),
            "program": top(prog, mine),
            "control": top(ctrl, ctrl_s),
            "control_bf16_state": top(bf16, bf16_s),
            "program_median": float(np.median(np.concatenate(prog))),
            "control_median": float(np.median(np.concatenate(ctrl))),
            "prompts": [len(rec.prompts[u]) for u in uids],
            "state_err_by_layer": {"program": by_layer(mine),
                                   "control_bf16_state": by_layer(bf16_s)}}
