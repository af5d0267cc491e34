"""Driver of the serving cells.

The system under test is ``repro.launch.serve.ContinuousServer`` on the
program's dense decoder (``repro.models.model_zoo``) with bf16 weights:
admission prefill, the paged KV store's writes and reads, the batched
cached decode step, the final norm and the logits, greedy picks.  The
weights are the seeded checkpoint of ``bench.checkpoint``, loaded into
the program's layout on the device in one jitted call.  The window
drives ``serve`` over the mix's offline queue, which never drains, and
stamps each token as the host receives it; it closes with the first
token that comes at or past its length, and that token's work, an
admission's whole prefill perhaps, is counted in it as its time is.
After the window closes
the same server goes on, untimed, until the requests it holds have
finished enough tokens for the check (at most ``DRAIN_S``): a long
answer that was in flight at the close is late, not missing.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import checkpoint as C
from bench import flops, generator, stats
from bench import weights as W

DRAIN_S = 60.0


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file in the
    keys of THUDM/glm-4-9b's ``config.json``."""
    from repro.configs.base import ModelConfig
    if cfg["torch_dtype"] != "bfloat16":
        raise ValueError(f"weights in {cfg['torch_dtype']}")
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=int(cfg["num_layers"]),
        d_model=int(cfg["hidden_size"]),
        num_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["multi_query_group_num"]),
        head_dim=int(cfg["kv_channels"]),
        d_ff=int(cfg["ffn_hidden_size"]),
        vocab_size=int(cfg["padded_vocab_size"]),
        pattern=("global",),
        qkv_bias=bool(cfg["add_qkv_bias"]),
        rope_fraction=float(cfg["partial_rotary_factor"]),
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=jnp.bfloat16)


def _sources(cfg: dict) -> tuple:
    """Where each leaf of the program's parameter tree comes from: the
    checkpoint tensor and the reference-layout flat index of each
    program-layout coordinate.  The program rotates pairs (j, j + 32)
    of a head's first 64 dims where the checkpoint interleaves them, so
    q and k take their head dims through ``rotary_source``."""
    s = C.dims(cfg)
    d, hd, F, V = s["d"], s["hd"], s["ff"], s["vocab"]
    Q, K = s["heads"] * hd, s["kv"] * hd

    def P(j):
        return C.rotary_source(j, hd, s["rot"])

    u = jnp.uint32
    layer = {
        "pre_norm/scale": ("attn_norm", lambda i: i),
        "attn/wq": ("q_w", lambda i, h, j: i * u(Q) + h * u(hd) + P(j)),
        "attn/bq": ("q_b", lambda h, j: h * u(hd) + P(j)),
        "attn/wk": ("k_w", lambda i, g, j: i * u(K) + g * u(hd) + P(j)),
        "attn/bk": ("k_b", lambda g, j: g * u(hd) + P(j)),
        "attn/wv": ("v_w", lambda i, g, j: i * u(K) + g * u(hd) + j),
        "attn/bv": ("v_b", lambda g, j: g * u(hd) + j),
        "attn/wo": ("o_w", lambda h, j, o: (h * u(hd) + j) * u(d) + o),
        "mlp_norm/scale": ("mlp_norm", lambda i: i),
        "mlp/wi_gate": ("gate_w", lambda i, f: i * u(F) + f),
        "mlp/wi_up": ("up_w", lambda i, f: i * u(F) + f),
        "mlp/wo": ("down_w", lambda f, o: f * u(d) + o),
    }
    top = {
        "embed/table": ("embed", lambda v, i: v * u(d) + i),
        "final_norm/scale": ("final_norm", lambda i: i),
        "lm_head": ("head", lambda i, v: i * u(V) + v),
    }
    return layer, top


def load_params(cfg: dict, model, seed: int):
    """The checkpoint in the program's parameter tree, made on the
    device in one jitted call from the seed, in bf16."""
    layer_src, top_src = _sources(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    stacks = shapes["stacks"]
    if list(stacks) != ["S0"] or list(stacks["S0"]) != ["L0"]:
        raise ValueError("expected one scanned stack of one layer kind")

    def make(words):
        def leaf(path, sds):
            key = "/".join(str(getattr(p, "key", p)) for p in path)
            iotas = [jax.lax.broadcasted_iota(jnp.uint32, sds.shape, ax)
                     for ax in range(len(sds.shape))]
            if key.startswith("stacks/S0/L0/"):
                name, index = layer_src[key[len("stacks/S0/L0/"):]]
                layer, iotas = iotas[0], iotas[1:]
            else:
                name, index = top_src[key]
                layer = 0
            return C.draw(words, name, index(*iotas),
                          layer).astype(sds.dtype)

        return jax.tree_util.tree_map_with_path(leaf, shapes)

    return jax.jit(make)(W.seed_words(seed))


@dataclasses.dataclass
class State:
    cfg: dict
    mix: dict
    seed: int
    dec: flops.Decoder
    server: object
    params: object
    queue: list


@dataclasses.dataclass
class Record:
    t_open: float
    t_close: float
    tokens: dict          # uid -> [token]
    times: dict           # uid -> [perf_counter s]
    prompts: dict         # uid -> prompt (np.int32)
    spans: list           # (name, t0, t1)
    dec: flops.Decoder
    events: list          # (uid, index) of each token, in arrival order
    served: dict          # uid -> [token], the window's and the drain's
    finished: set         # uids whose last token came by the drain's end

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open


def setup(cfg: dict, mix: dict, seed: int) -> State:
    from repro.launch.serve import ContinuousServer
    from repro.models import model_zoo
    model = model_zoo.build(model_config(cfg))
    params = load_params(cfg, model, seed)
    server = ContinuousServer(
        model, num_slots=int(mix["slots"]),
        capacity=int(mix["capacity"]),
        page_size=int(mix["page_size"]), quant="none")
    vocab = int(cfg["padded_vocab_size"])
    # Warm pass of the mix's own shapes: every prompt bucket's prefill,
    # the decode step on every slot, the store's eager ops.
    for _ in server.serve(params, generator.warm_requests(mix, seed,
                                                          vocab)):
        pass
    return State(cfg, mix, seed, flops.Decoder(cfg), server, params,
                 generator.requests(mix, seed, vocab))


def window(state: State, seconds: float) -> Record:
    tokens, times, spans, events = {}, {}, [], []
    finished = set()
    prompts = {r["uid"]: r["prompt"] for r in state.queue}
    gen = state.server.serve(state.params, state.queue)
    t_open = time.perf_counter()
    t_end = t_open + seconds
    try:
        while True:
            t0 = time.perf_counter()
            ev = next(gen)
            t1 = time.perf_counter()
            tokens.setdefault(ev.uid, []).append(ev.token)
            times.setdefault(ev.uid, []).append(t1)
            events.append((ev.uid, ev.index))
            if ev.done:
                finished.add(ev.uid)
            spans.append(("bench.admit" if ev.index == 0
                          else "bench.decode", t0, t1))
            if t1 >= t_end:
                break
        served = {u: list(t) for u, t in tokens.items()}
        _drain(gen, served, finished, state.mix["check"], t1)
    except StopIteration:
        raise RuntimeError("the queue drained inside the window: the "
                           "mix needs more rounds") from None
    finally:
        gen.close()
    return Record(t_open, t1, tokens, times,
                  {u: prompts[u] for u in served}, spans, state.dec,
                  events, served, finished)


def _drain(gen, served, finished, want, t_close) -> None:
    """Serve on past the close until the finished requests fill the
    check's sample (its count or its tokens, as ``sample`` stops at
    either), or ``DRAIN_S`` has passed."""
    def enough():
        return len(finished) >= want["max_requests"] or \
            sum(len(served[u]) for u in finished) >= want["tokens"]

    while not enough() and time.perf_counter() < t_close + DRAIN_S:
        ev = next(gen)
        served.setdefault(ev.uid, []).append(ev.token)
        if ev.done:
            finished.add(ev.uid)


def end_to_end(rec: Record) -> dict:
    w = rec.window_s
    gaps = stats.intertoken_gaps(rec.times)
    return {
        "output_tok_s": sum(map(len, rec.tokens.values())) / w,
        "prompt_tok_s": sum(len(rec.prompts[u]) for u in rec.tokens) / w,
        "itl_p95_ms": stats.percentile(gaps, 95) * 1e3,
    }


def decode_steps(rec: Record) -> list:
    """The window's batched decode steps, each as the context (earlier
    positions) of every slot it decoded.  ``serve`` yields a step's
    admissions first, then one token per live slot; so a step begins
    at the first decode token after an admission, or where a request
    decodes a second time."""
    steps, uids, fresh = [], set(), True
    for uid, i in rec.events:
        if i == 0:
            fresh = True
            continue
        if fresh or uid in uids:
            steps.append([])
            uids, fresh = set(), False
        uids.add(uid)
        steps[-1].append(len(rec.prompts[uid]) + i - 1)
    return steps


def work(rec: Record) -> dict:
    """Model FLOPs the window's tokens needed (prefills of its
    admissions, decode of every later token), its prompt tokens, and
    the bytes and FLOPs its decode steps needed."""
    dec = rec.dec
    fl = 0
    for uid, toks in rec.tokens.items():
        n = len(rec.prompts[uid])
        fl += dec.prefill_flops(n)
        fl += sum(dec.decode_flops(n + i - 1) for i in range(1, len(toks)))
    steps = decode_steps(rec)
    return {"flops": fl,
            "prompt_tokens": sum(len(rec.prompts[u]) for u in rec.tokens),
            "output_tokens": sum(map(len, rec.tokens.values())),
            "decode_steps": len(steps),
            "decode_bytes": sum(dec.decode_step_bytes(c) for c in steps),
            "decode_flops": sum(dec.decode_flops(n) for c in steps
                                for n in c)}


def release(state: State) -> None:
    """Free the program's state on the device."""
    state.server = state.params = None
    gc.collect()


def sample(rec: Record, mix: dict, seed: int) -> list:
    """Finished requests to check: the one with the most served tokens,
    then others in the seed's order, up to the mix's ``check`` sizes."""
    want = mix["check"]
    done = sorted(rec.finished, key=lambda u: (-len(rec.served[u]), u))
    if not done:
        return []
    rest = done[1:]
    order = np.random.default_rng(
        np.random.SeedSequence([seed & 0xFFFFFFFF, seed >> 32, 5])
    ).permutation(len(rest))
    picked = [done[0]] + [rest[i] for i in order]
    out, served = [], 0
    for uid in picked:
        if len(out) >= want["max_requests"] or served >= want["tokens"]:
            break
        out.append(uid)
        served += len(rec.served[uid])
    return out


def check(rec: Record, cfg: dict, mix: dict, seed: int, ref,
          released=None) -> tuple:
    """The widest gap by which a served token's logit lies below the
    reference's best, over a sample of finished requests."""
    limit = float(cfg["limits"]["max_logit_gap"])
    uids = sample(rec, mix, seed)
    if not uids:
        return {"finished_requests": (0, 1)}, 1
    seqs = [(np.concatenate([rec.prompts[u],
                             np.asarray(rec.served[u], np.int32)]),
             len(rec.prompts[u])) for u in uids]
    gaps = ref.served_gaps(cfg, seed, seqs)
    worst = [float(np.max(g)) for g in gaps]
    failed = sum(1 for g in worst if not g <= limit)
    return {"max_logit_gap": (max(worst), limit)}, failed


def readings(cfg: dict, mix: dict, seed: int, seconds: float,
             ref) -> dict:
    """The number ``check`` compares, for the program and for the
    control (the plain reference one precision below, which needs no
    decode: at each position of the same prompts and served tokens it
    reads the gap of the token it puts first)."""
    state = setup(cfg, mix, seed)
    rec = window(state, seconds)
    release(state)
    del state
    uids = sample(rec, mix, seed)
    if not uids:
        return {"answers": 0, "program": {}, "control": {}}
    seqs = [(np.concatenate([rec.prompts[u],
                             np.asarray(rec.served[u], np.int32)]),
             len(rec.prompts[u])) for u in uids]
    prog = ref.served_gaps(cfg, seed, seqs)
    ctrl = ref.served_gaps(cfg, seed, seqs, control=True)
    return {"answers": int(sum(len(g) for g in prog)),
            "program": {"max_logit_gap": max(float(g.max())
                                             for g in prog)},
            "control": {"max_logit_gap": max(float(g.max())
                                             for g in ctrl)},
            "program_median": float(np.median(np.concatenate(prog))),
            "control_median": float(np.median(np.concatenate(ctrl)))}


def attempted(rec: Record) -> int:
    return len(rec.tokens)
