"""Serving: fixed-batch and continuous-batching decode loops.

``Server`` packages jitted prefill/decode for a fixed batch geometry
(a fleet of fixed-shape servers + a router).  Greedy or temperature
sampling; per-slot stop handling pins every post-EOS position to the
stop id so a batch of heterogeneous requests drains correctly.

``ContinuousServer`` is the production decode loop: a slot-based
scheduler admits requests into freed slots *mid-stream* and evicts
finished ones, KV state lives in a paged store
(``repro.models.kv_cache.PagedKVCache`` — fixed-size pages, per-slot
page tables, quantize-on-write), and tokens stream back per step
through an iterator (``serve``) or callback (``generate``) API.  See
docs/serving.md for the scheduler's slot lifecycle and the page-table
layout.

Scoring (``Server.score`` / ``batched_logprobs``) normalises the
batched logits through the TC reduction path: the log-softmax
normaliser's sum over vocab and the per-sequence fold both ride
``repro.core.integration.reduce_sum`` (the batched ones-contraction on
the matrix unit, mesh-keyed plans under a live mesh) instead of ad-hoc
vector-lane sums.  Both scoring entry points take an ``objective``
(``repro.core.autotune.LatencyObjective`` or a plain SLO in ms): under
``method='auto'`` the vocab reduction then resolves a *latency-keyed*
plan (``|lat:`` suffix) — prefill-shaped (B, S, V) logits and
single-token decode (B, 1, V) logits bucket to different problem
sizes, so each shape gets its own SLO-constrained plan.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from collections import deque
from typing import Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import integration as ci
from repro.distributed import sharding as shd
from repro.launch.compile_cache import use_compile_cache
from repro.models import model_zoo
from repro.models import transformer as T
from repro.models.kv_cache import PagedKVCache


def batched_logprobs(logits, tokens, *, method: str = "auto",
                     precision=None, objective=None,
                     bucket: str = "pow2") -> jax.Array:
    """Per-token log-probabilities: (B, S, V) logits + (B, S) ids →
    (B, S) f32.

    The log-softmax normaliser logZ = log Σ_v exp(l_v − m) + m is the
    serving stack's per-position arithmetic reduction; its sum over
    vocab routes through the TC dispatch layer
    (``repro.core.integration.reduce_sum`` with ``axis=-1`` — the
    batched ones-contraction, reshape-free, so sharded logits keep
    their layout and ``method='auto'`` resolves a mesh-keyed plan
    under a live mesh).  Accumulation is f32 throughout (the precision
    contract); the max-shift keeps exp in range.  ``precision``
    threads an ``repro.core.precision.MmaPolicy`` to the vocab
    reduction — a scoring service that must bound its normaliser
    error passes a budget policy here and the auto plan honours it.
    ``objective`` threads a latency SLO the same way (a
    ``repro.core.autotune.LatencyObjective``, its signature string, or
    a number of milliseconds): the auto plan is then the most accurate
    candidate meeting the SLO for *this* logits shape.  ``bucket``
    names the shape-bucketing policy the plan is keyed under
    (``repro.core.autotune.bucket_cap``; ``None`` for exact keys).
    """
    lf = logits.astype(jnp.float32)
    shift = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    z = ci.reduce_sum(jnp.exp(lf - shift), axis=-1, method=method,
                      precision=precision, objective=objective,
                      bucket=bucket)
    logz = jnp.log(z) + shift[..., 0]
    tok = jnp.take_along_axis(
        lf, tokens[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return tok - logz


@dataclasses.dataclass
class Server:
    model: object
    mesh: Optional[object] = None
    temperature: float = 0.0
    extra_capacity: int = 64   # decode headroom the prefill allocates

    def __post_init__(self):
        m = self.model

        def prefill(params, batch):
            with shd.axis_rules(self.mesh):
                return m.prefill(params, batch,
                                 extra_capacity=self.extra_capacity)

        def decode(params, batch):
            with shd.axis_rules(self.mesh):
                return m.decode_step(params, batch)

        def full_logits(params, batch):
            with shd.axis_rules(self.mesh):
                return m.logits(params, batch)

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode, donate_argnums=())
        self._logits = jax.jit(full_logits)

    def score(self, params, tokens, *, mask=None,
              extras: Optional[dict] = None,
              method: str = "auto", precision=None,
              objective=None, bucket: str = "pow2") -> jax.Array:
        """Total log-probability of each sequence under the model
        (teacher forcing): one full-sequence forward (the model's
        ``logits`` path — ``prefill`` keeps only the last position),
        ``batched_logprobs`` normalisation over vocab, then a per-row
        fold of the token logprobs — both reductions on the
        registry-dispatched TC path.  ``mask`` (optional, (B, S) with
        1 = scored position) zeroes padding before the fold; ``extras``
        carries the modality inputs enc-dec / vision configs require
        (``src_embeds`` / ``vision_embeds``), exactly like
        ``generate``.  Returns (B,) f32.
        """
        toks = jnp.asarray(tokens, jnp.int32)
        batch = {"tokens": toks}
        if extras:
            batch.update(extras)
        logits = self._logits(params, batch)
        lp = batched_logprobs(logits[:, :-1], toks[:, 1:],
                              method=method, precision=precision,
                              objective=objective, bucket=bucket)
        if mask is not None:
            lp = lp * jnp.asarray(mask, jnp.float32)[:, 1:]
        return ci.reduce_sum(lp, axis=-1, method=method,
                             precision=precision, objective=objective,
                             bucket=bucket)

    def _sample(self, logits, key):
        if self.temperature <= 0.0:
            return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits[:, -1, :] / self.temperature).astype(jnp.int32)

    def generate(self, params, prompts: np.ndarray, *, max_new: int = 32,
                 eos_id: Optional[int] = None, seed: int = 0,
                 extras: Optional[dict] = None):
        """prompts: (B, S) int32. Returns (B, <=max_new) generated ids.

        Rows that hit ``eos_id`` before the rest of the batch stay
        pinned to ``eos_id``: the sampled continuation of a finished
        row is garbage (the model was never asked to continue past its
        stop), so every post-EOS position is overwritten before it is
        emitted or fed back as the next decode input.
        """
        b, s = prompts.shape
        batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
        if extras:
            batch.update(extras)
        key = jax.random.PRNGKey(seed)
        logits, caches = self._prefill(params, batch)
        out = []
        done = np.zeros((b,), bool)
        key, k0 = jax.random.split(key)
        tok = self._sample(logits, k0)
        for i in range(max_new):
            t = np.asarray(tok)
            if eos_id is not None:
                t = np.where(done, np.int32(eos_id), t)
                done |= t == eos_id
            out.append(t)
            if eos_id is not None and done.all():
                break
            step_batch = {"token": jnp.asarray(t)[:, None],
                          "pos": jnp.asarray(s + i, jnp.int32),
                          "caches": caches}
            logits, caches = self._decode(params, step_batch)
            key, ki = jax.random.split(key)
            tok = self._sample(logits, ki)
        return np.stack(out, axis=1)


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request for the continuous engine."""
    uid: int
    prompt: np.ndarray          # (S,) int32 token ids
    max_new: int = 32


@dataclasses.dataclass(frozen=True)
class TokenEvent:
    """One streamed token: request ``uid`` emitted its ``index``-th
    output token.  ``done`` marks the request's final token (EOS or
    ``max_new`` reached); ``logprob`` is filled when the engine runs
    with ``logprobs=True``."""
    uid: int
    index: int
    token: int
    done: bool
    logprob: Optional[float] = None


@dataclasses.dataclass
class _Slot:
    """Scheduler state for one live slot (see docs/serving.md)."""
    uid: int
    last_tok: int               # feeds the next decode step
    next_pos: int               # absolute position it will occupy
    n_out: int                  # tokens emitted so far
    max_new: int


class ContinuousServer:
    """Continuous-batching decode engine over a paged KV store.

    A fixed bank of ``num_slots`` decode slots steps in lock-step
    (one batched per-row decode per iteration, each slot at its own
    absolute position); a scheduler admits pending requests into free
    slots *between* steps — a request finishing at step t frees its
    slot for a new admission at step t+1, no batch drain — and evicts
    finished ones, returning their pages to the pool.

    Admission runs the request's prompt as a batch-1 prefill with
    ``extra_capacity`` topping the prompt up to ``capacity``, then
    quantizes the whole prompt's KV into the slot's pages
    (``PagedKVCache.write_slot``).  Each decode step reads the paged
    store (``as_dense`` — gather + compensated dequant), runs the
    model's per-row decode, and writes back only the one new token per
    live slot (``write_token``), so quantization error never
    compounds.  ``quant='none'`` stores raw KV and the engine's
    streamed tokens are bit-identical to draining the same requests
    one at a time through ``Server.generate`` (greedy); ``'int8'``
    adds codes+scale (+ bf16 residual under a ``split_words >= 2``
    policy) quantize-on-write.

    Sampling is per-request deterministic: temperature 0 is greedy;
    otherwise the categorical key is folded from (seed, uid, index),
    so a request's sample stream does not depend on which slot or
    step served it.

    ``latency_slo_ms`` arms the autotuner's latency objective for the
    scoring reductions (``logprobs=True``): admission scores
    prefill-shaped logits, the decode loop scores (num_slots, 1, V)
    logits, and each resolves its own ``|lat:``-keyed plan.

    ``attn_method`` rebuilds the model with its attention routed
    through the named registry engine (or ``'auto'``): prefill and the
    per-step paged decode then share one code path — the decode step
    dequantizes the paged store to a dense view and the fused kernel
    masks ring-buffer slots past ``kv_len`` in-kernel.  The same
    ``latency_slo_ms`` keys the attention plans, and prefill- vs
    decode-shaped problems bucket to distinct plan keys.

    ``norm_matmul_method`` does the same for the fused
    rmsnorm->matmul block boundary (the ``norm_matmul`` op): the
    rebuilt model routes its MLP up/gate projections and the MLA
    absorbed-form query chain through the named engine, the SLO
    threads into the decode-shape plans as
    ``ModelConfig.norm_matmul_slo_ms``, and ``warmup`` pre-resolves
    the decode- and prefill-shaped norm_matmul plans alongside the
    scoring hot set.

    ``bucket`` names the plan store's shape-bucketing policy
    (``repro.core.autotune.bucket_cap``) every auto plan the engine
    resolves is keyed under; ``warmup`` (see the method) pre-resolves
    the scoring-plan hot set and pre-compiles bucketed prefill shapes
    before traffic; ``background_sweeps=True`` attaches a
    ``repro.core.autotune.SweepWorker`` to the plan registry so
    model-cost plans resolved on the hot path are upgraded to measured
    plans in the background — ``close()`` (or the context-manager
    form) detaches and stops it, and can never deadlock on an
    in-flight sweep (the worker follows the data-pipeline prefetch
    shutdown pattern).
    """

    def __init__(self, model, *, num_slots: int = 4, capacity: int = 128,
                 page_size: int = 16, quant: str = "none",
                 precision=None, mesh=None, temperature: float = 0.0,
                 latency_slo_ms: Optional[float] = None,
                 logprobs: bool = False, seed: int = 0,
                 attn_method: Optional[str] = None,
                 norm_matmul_method: Optional[str] = None,
                 bucket: str = "pow2",
                 background_sweeps: bool = False):
        cfg = model.cfg
        if cfg.is_encdec or cfg.vision_tokens:
            raise ValueError(
                "ContinuousServer serves text decoders; enc-dec and "
                "vision configs need per-request memory (use Server)")
        if attn_method is not None or norm_matmul_method is not None:
            # Route prefill and decode through the requested registry
            # engines (e.g. 'fused_pallas' for the paged-decode fused
            # attention kernel and/or the fused norm->matmul block
            # boundary, or 'auto' under the same latency SLO that keys
            # the scoring reductions).  The engines take whole
            # (de)quantized tensors, so an engine-side policy never
            # word-splits: cap split_words at 1 — the residual words
            # belong to the KV store's quantizer, which keeps the
            # caller's ``precision`` untouched.
            pol = precision
            if pol is not None and \
                    getattr(pol, "split_words", 1) != 1:
                pol = dataclasses.replace(pol, split_words=1)
            repl: dict = {}
            if attn_method is not None:
                repl.update(attn_method=attn_method,
                            attn_precision=pol,
                            attn_slo_ms=latency_slo_ms)
            if norm_matmul_method is not None:
                repl.update(norm_matmul_method=norm_matmul_method,
                            norm_matmul_precision=pol,
                            norm_matmul_slo_ms=latency_slo_ms)
            cfg = dataclasses.replace(cfg, **repl)
            model = model_zoo.build(cfg)
        self.model = model
        self.cfg = cfg
        self.mesh = mesh
        self.num_slots = int(num_slots)
        self.capacity = int(capacity)
        self.page_size = int(page_size)
        self.quant = quant
        self.precision = precision
        self.temperature = float(temperature)
        self.objective = latency_slo_ms
        self.logprobs = bool(logprobs)
        self.seed = int(seed)
        self.bucket = bucket
        self._sweeper = None
        self._sweep_failed_closed = 0
        if background_sweeps:
            from repro.core import autotune
            reg = autotune.default_registry()
            self._sweeper = autotune.SweepWorker(reg)
            reg.sweep_worker = self._sweeper
        m = model

        def prefill(params, batch, extra_capacity):
            with shd.axis_rules(self.mesh):
                return m.prefill(params, batch,
                                 extra_capacity=extra_capacity)

        def decode(params, batch):
            with shd.axis_rules(self.mesh):
                return m.decode_step(params, batch)

        self._prefill = jax.jit(prefill,
                                static_argnames=("extra_capacity",))
        self._decode = jax.jit(decode)

    # ----------------------------------------------- warmup/lifecycle

    def warmup(self, params=None, *, prompt_lens=None) -> dict:
        """Pre-resolve the serving hot set before traffic arrives.

        Plan side (always): the scoring reductions' two hot shapes —
        admission scores (1, 1, V) last-position logits, the decode
        loop (num_slots, 1, V) — run once through the real scoring
        path, so their ``|lat:``-keyed plans are resolved (and the
        scoring reductions compiled) under the server's bucket policy.

        Compile side (when ``params`` is given): one batch-1 prefill
        per bucketed prompt length — default: the ``self.bucket``
        bucket caps that fit ``capacity`` — populates the jit cache,
        so admitting a bucketed request stream
        (``repro.data.pipeline.synthetic_requests`` with the same
        ``bucket``) never compiles mid-traffic.

        Returns ``{"plans", "scoring_shapes", "prefill_compiles"}``
        (``plans`` = tuning events this warmup caused in the default
        registry).
        """
        from repro.core import autotune
        reg = autotune.default_registry()
        before = len(reg)
        V = self.cfg.vocab_size
        shapes = ((1, 1, V), (self.num_slots, 1, V))
        for shape in shapes:
            self._lp(jnp.zeros(shape, jnp.float32),
                     jnp.zeros(shape[:2], jnp.int32))
        if getattr(self.cfg, "norm_matmul_method", ""):
            # Pre-resolve the fused block-boundary plans for the two
            # hot norm_matmul shapes — decode (num_slots rows) and
            # full-capacity prefill (capacity rows) — under the same
            # SLO/bucket that keys the scoring reductions.
            d = self.cfg.d_model
            autotune.warmup(
                "norm_matmul",
                (self.num_slots * d, self.capacity * d),
                registry=reg,
                policy=getattr(self.cfg, "norm_matmul_precision", None),
                objective=self.objective, bucket=self.bucket)
        lens: tuple = ()
        if params is not None:
            if prompt_lens is None:
                caps = {min(autotune.bucket_cap(L, self.bucket),
                            self.capacity - 1)
                        for L in range(1, self.capacity)}
                lens = tuple(sorted(caps))
            else:
                lens = tuple(sorted(set(int(L) for L in prompt_lens)))
            for L in lens:
                tokens = jnp.zeros((1, L), jnp.int32)
                self._prefill(params, {"tokens": tokens},
                              self.capacity - L)
        return {"plans": len(reg) - before, "scoring_shapes": shapes,
                "prefill_compiles": len(lens)}

    @property
    def sweep_failures(self) -> int:
        """Background sweeps that failed (0 without a worker); counted
        across ``close()``."""
        live = 0 if self._sweeper is None else self._sweeper.failed
        return self._sweep_failed_closed + live

    def close(self) -> None:
        """Detach and stop the background sweep worker (idempotent;
        safe with sweeps still in flight — the worker's shutdown
        drains rather than joins on pending work)."""
        if self._sweeper is None:
            return
        from repro.core import autotune
        reg = autotune.default_registry()
        if reg.sweep_worker is self._sweeper:
            reg.sweep_worker = None
        self._sweeper.close()
        self._sweep_failed_closed += self._sweeper.failed
        self._sweeper = None

    def __enter__(self) -> "ContinuousServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------ pieces

    def _new_store(self) -> PagedKVCache:
        template = jax.eval_shape(lambda: T.init_decoder_cache(
            self.cfg, self.num_slots, self.capacity, 0))
        return PagedKVCache(template, num_slots=self.num_slots,
                            page_size=self.page_size, quant=self.quant,
                            precision=self.precision)

    def _pick(self, row_logits, uid: int, index: int) -> int:
        """Sample one token from a (V,) logits row."""
        if self.temperature <= 0.0:
            return int(jnp.argmax(row_logits))
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(self.seed), uid),
            index)
        return int(jax.random.categorical(
            key, row_logits / self.temperature))

    def _lp(self, logits, tokens) -> jax.Array:
        """(B,) logprob of each row's token under its (B, 1, V) or
        (1, S, V) logits — the latency-objective scoring reduction."""
        lp = batched_logprobs(logits, tokens, method="auto",
                              precision=self.precision,
                              objective=self.objective,
                              bucket=self.bucket)
        return lp[:, -1]

    # -------------------------------------------------------- loop

    def serve(self, params, requests, *,
              eos_id: Optional[int] = None) -> Iterator[TokenEvent]:
        """Stream tokens for ``requests`` (iterable of ``Request``).

        Yields one ``TokenEvent`` per generated token, in scheduler
        order: admissions (slot order), then the step's decode
        results (slot order), each step.  The iterator drives the
        engine — consuming it lazily backpressures the decode loop.
        Items may be ``Request`` objects or the equivalent dicts
        (``repro.data.pipeline.synthetic_requests`` yields the
        latter).
        """
        pending = deque(r if isinstance(r, Request) else Request(**r)
                        for r in requests)
        for r in pending:
            need = len(r.prompt) + r.max_new
            if r.max_new < 1:
                raise ValueError(f"request {r.uid}: max_new must be >= 1")
            if need > self.capacity:
                raise ValueError(
                    f"request {r.uid}: prompt {len(r.prompt)} + "
                    f"max_new {r.max_new} exceeds capacity "
                    f"{self.capacity}")
        store = self._new_store()
        slots: dict[int, _Slot] = {}

        while pending or slots:
            # --- admission: fill every free slot from the queue
            for s in range(self.num_slots):
                if not pending or s in slots:
                    continue
                req = pending.popleft()
                prompt = np.asarray(req.prompt, np.int32)
                L = prompt.shape[0]
                logits, caches = self._prefill(
                    params, {"tokens": jnp.asarray(prompt[None])},
                    self.capacity - L)
                store.alloc_slot(s)
                store.write_slot(s, caches)
                tok = self._pick(logits[0, -1], req.uid, 0)
                lp = None
                if self.logprobs:
                    lp = float(self._lp(
                        logits, jnp.asarray([[tok]], jnp.int32))[0])
                done = (eos_id is not None and tok == eos_id) \
                    or req.max_new == 1
                yield TokenEvent(req.uid, 0, tok, done, lp)
                if done:
                    store.free_slot(s)
                else:
                    slots[s] = _Slot(req.uid, tok, L, 1, req.max_new)
            if not slots:
                continue

            # --- one batched per-row decode step for the live slots
            toks = np.zeros((self.num_slots, 1), np.int32)
            pos = np.zeros((self.num_slots,), np.int32)
            for s, st in slots.items():
                toks[s, 0] = st.last_tok
                pos[s] = st.next_pos
            dense = store.as_dense()
            logits, caches = self._decode(
                params, {"token": jnp.asarray(toks),
                         "pos": jnp.asarray(pos), "caches": dense})
            lps = None
            picks = {s: self._pick(logits[s, -1], st.uid, st.n_out)
                     for s, st in slots.items()}
            if self.logprobs:
                lpt = np.zeros((self.num_slots, 1), np.int32)
                for s, t in picks.items():
                    lpt[s, 0] = t
                lps = np.asarray(self._lp(logits, jnp.asarray(lpt)))
            for s in sorted(slots):
                st = slots[s]
                store.write_token(caches, s, st.next_pos)
                t = picks[s]
                idx = st.n_out
                st.n_out += 1
                done = (eos_id is not None and t == eos_id) \
                    or st.n_out >= st.max_new
                yield TokenEvent(st.uid, idx, t, done,
                                 None if lps is None else float(lps[s]))
                if done:
                    store.free_slot(s)
                    del slots[s]
                else:
                    st.last_tok = t
                    st.next_pos += 1

    def generate(self, params, requests, *,
                 eos_id: Optional[int] = None,
                 on_token: Optional[Callable] = None) -> dict:
        """Drain ``requests``; returns {uid: (n,) int32 tokens}.

        ``on_token`` (optional) is called with every ``TokenEvent`` as
        it is produced — the callback form of the streaming API.
        """
        out: dict[int, list] = {}
        for ev in self.serve(params, requests, eos_id=eos_id):
            out.setdefault(ev.uid, []).append(ev.token)
            if on_token is not None:
                on_token(ev)
        return {uid: np.asarray(toks, np.int32)
                for uid, toks in out.items()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching engine (paged KV store)")
    ap.add_argument("--num-slots", type=int, default=4)
    ap.add_argument("--capacity", type=int, default=64)
    ap.add_argument("--quant", choices=("none", "int8"), default="none")
    ap.add_argument("--latency-slo-ms", type=float, default=None)
    ap.add_argument("--attn-method", default=None,
                    help="attention registry engine for the continuous "
                         "engine (fused_pallas | unfused_mma | vpu | "
                         "auto)")
    ap.add_argument("--norm-matmul-method", default=None,
                    help="norm_matmul registry engine for the fused "
                         "rmsnorm->matmul block boundary "
                         "(fused_pallas | unfused_mma | vpu | auto)")
    ap.add_argument("--warmup", action="store_true",
                    help="pre-resolve scoring plans and pre-compile "
                         "bucketed prefill shapes before serving")
    ap.add_argument("--background-sweeps", action="store_true",
                    help="upgrade model-cost plans to measured plans "
                         "in a background sweep worker")
    ap.add_argument("--plan-store", default=None,
                    help="shared autotune plan-store JSON: merged in "
                         "at startup, saved (atomic, file-locked, "
                         "merge-on-save) at exit")
    args = ap.parse_args()
    use_compile_cache()

    if args.plan_store:
        from repro.core import autotune
        autotune.bind_default_registry(args.plan_store)

    from repro.configs import registry
    cfg = registry.get_config(args.arch, smoke=not args.full)
    model = model_zoo.build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)

    if args.continuous:
        eng = ContinuousServer(
            model, num_slots=args.num_slots, capacity=args.capacity,
            quant=args.quant, latency_slo_ms=args.latency_slo_ms,
            logprobs=args.latency_slo_ms is not None,
            attn_method=args.attn_method,
            norm_matmul_method=args.norm_matmul_method,
            background_sweeps=args.background_sweeps)
        with eng:
            if args.warmup:
                t0 = time.time()
                info = eng.warmup(params)
                print(f"warmup: {info['plans']} plans tuned, "
                      f"{info['prefill_compiles']} prefill shapes "
                      f"compiled in {time.time() - t0:.2f}s")
            reqs = [Request(uid=i, prompt=prompts[i],
                            max_new=args.max_new)
                    for i in range(args.batch)]
            t0 = time.time()
            outs = eng.generate(params, reqs)
            dt = time.time() - t0
        n = sum(len(t) for t in outs.values())
        print(f"continuous: {n} tokens from {len(reqs)} requests in "
              f"{dt:.2f}s ({n / dt:.1f} tok/s)")
        for uid in sorted(outs)[:2]:
            print(uid, outs[uid])
        if args.plan_store:
            autotune.default_registry().save(args.plan_store)
        return

    extras = {}
    if cfg.vision_tokens:
        extras["vision_embeds"] = jnp.asarray(
            rng.standard_normal((args.batch, cfg.vision_tokens,
                                 cfg.d_model)), jnp.bfloat16)
    if cfg.is_encdec:
        extras["src_embeds"] = jnp.asarray(
            rng.standard_normal((args.batch, args.prompt_len,
                                 cfg.d_model)), jnp.bfloat16)
    srv = Server(model)
    t0 = time.time()
    toks = srv.generate(params, prompts, max_new=args.max_new,
                        extras=extras)
    dt = time.time() - t0
    print(f"generated {toks.shape} in {dt:.2f}s "
          f"({toks.size / dt:.1f} tok/s)")
    print(toks[:2])


if __name__ == "__main__":
    main()
