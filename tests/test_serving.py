"""Serving-stack tests: continuous batching, paged KV, EOS, scoring.

The acceptance surface of the production decode loop:
  * a ragged/staggered request stream drained by ``ContinuousServer``
    yields per-request tokens bit-identical to running each request
    alone through the fixed-batch ``Server.generate`` (greedy,
    ``quant='none'``);
  * the int8 paged store (codes + bf16 residual) reproduces the same
    stream for bf16 caches — quantize-on-write is exact there;
  * scheduler invariants: slots are never re-allocated before
    eviction, per-request token order is preserved, admissions reuse
    freed slots mid-stream;
  * ``Server.generate`` pins every post-EOS position to ``eos_id``
    under heterogeneous stop steps (regression: finished rows used to
    keep sampling garbage);
  * ``Server.score`` mask semantics against a hand-rolled fp64
    oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.data.pipeline import synthetic_requests
from repro.launch.serve import (ContinuousServer, Request, Server,
                                batched_logprobs)
from repro.models import model_zoo
from repro.models.kv_cache import PagedKVCache

CAP = 40


@pytest.fixture(scope="module")
def served_model():
    cfg = registry.get_config("gemma2-2b", smoke=True)
    model = model_zoo.build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, n=4, seed=0, max_new=10):
    return [Request(**d) for d in synthetic_requests(
        cfg.vocab_size, n=n, seed=seed, min_len=3, max_len=12,
        min_new=2, max_new=max_new, stagger=1)]


def _one_at_a_time(model, params, reqs, capacity=CAP):
    """The bit-identity reference: each request alone, fixed batch 1,
    prefill headroom matched to the engine's slot capacity."""
    out = {}
    for r in reqs:
        srv = Server(model, extra_capacity=capacity - len(r.prompt))
        out[r.uid] = srv.generate(params, r.prompt[None],
                                  max_new=r.max_new)[0]
    return out


def test_continuous_matches_one_at_a_time_bitwise(served_model):
    cfg, model, params = served_model
    reqs = _requests(cfg, n=5)
    eng = ContinuousServer(model, num_slots=2, capacity=CAP,
                           page_size=8, quant="none")
    got = eng.generate(params, reqs)
    ref = _one_at_a_time(model, params, reqs)
    assert sorted(got) == sorted(ref)
    for uid in ref:
        assert got[uid].shape == ref[uid].shape, uid
        assert np.array_equal(got[uid], ref[uid]), uid


def test_fused_norm_matmul_decode_matches_one_at_a_time_bitwise(
        served_model, fresh_plan_registry):
    """ISSUE-10: routing the block boundary through the fused
    norm->matmul kernel must not perturb a single served token —
    ContinuousServer with ``norm_matmul_method='fused_pallas'`` streams
    tokens bit-identical to draining the same (rebuilt, fused) model
    one request at a time through Server.generate, and warmup
    pre-resolves the op's decode/prefill plans."""
    cfg, model, params = served_model
    reqs = _requests(cfg, n=4, seed=7)
    eng = ContinuousServer(model, num_slots=2, capacity=CAP,
                           page_size=8, quant="none",
                           norm_matmul_method="fused_pallas")
    assert eng.cfg.norm_matmul_method == "fused_pallas"
    info = eng.warmup()
    from repro.core import autotune
    keys = [k for k, _ in autotune.default_registry().items()]
    assert any(k.startswith("norm_matmul") for k in keys), keys
    got = eng.generate(params, reqs)
    # the reference drains eng.model — the rebuilt fused-config model;
    # the knobs change no param specs, so params are shared
    ref = _one_at_a_time(eng.model, params, reqs)
    assert sorted(got) == sorted(ref)
    for uid in ref:
        assert got[uid].shape == ref[uid].shape, uid
        assert np.array_equal(got[uid], ref[uid]), uid


def test_int8_paged_store_matches_dense_stream(served_model):
    """bf16 KV survives int8+residual quantize-on-write exactly, so
    the quantized engine streams the identical tokens; the store-level
    error-budget bound is covered in test_kv_cache."""
    cfg, model, params = served_model
    reqs = _requests(cfg, n=3, seed=1)
    exact = ContinuousServer(model, num_slots=2, capacity=CAP,
                             page_size=8, quant="none")
    quant = ContinuousServer(model, num_slots=2, capacity=CAP,
                             page_size=8, quant="int8")
    a = exact.generate(params, reqs)
    b = quant.generate(params, reqs)
    for uid in a:
        assert np.array_equal(a[uid], b[uid]), uid


class _RecordingStore(PagedKVCache):
    def __init__(self, *a, trace=None, **kw):
        super().__init__(*a, **kw)
        self._trace = trace if trace is not None else []

    def alloc_slot(self, slot):
        self._trace.append(("alloc", slot))
        return super().alloc_slot(slot)

    def free_slot(self, slot):
        self._trace.append(("free", slot))
        return super().free_slot(slot)


def test_scheduler_admit_evict_invariants(served_model):
    cfg, model, params = served_model
    reqs = _requests(cfg, n=6, seed=2, max_new=6)
    eng = ContinuousServer(model, num_slots=2, capacity=CAP,
                           page_size=8, quant="none")
    trace = []
    base_new_store = eng._new_store

    def recording_store():
        store = base_new_store()
        store.__class__ = _RecordingStore
        store._trace = trace
        return store

    eng._new_store = recording_store
    events = []
    out = eng.generate(params, reqs, on_token=events.append)

    # every request drained, token order preserved per request
    assert sorted(out) == [r.uid for r in reqs]
    seen = {}
    for ev in events:
        assert ev.index == seen.get(ev.uid, 0), (ev.uid, ev.index)
        seen[ev.uid] = ev.index + 1
    for r in reqs:
        assert seen[r.uid] == len(out[r.uid]) <= r.max_new

    # slot lifecycle: a slot is allocated only when free, freed only
    # when live, and 6 requests through 2 slots forces mid-stream
    # reuse of freed slots
    live = set()
    for op, slot in trace:
        if op == "alloc":
            assert slot not in live, trace
            live.add(slot)
        else:
            assert slot in live, trace
            live.discard(slot)
        assert len(live) <= eng.num_slots
    assert not live                       # everything evicted at end
    assert sum(op == "alloc" for op, _ in trace) == len(reqs)


def test_streaming_iterator_is_lazy_and_tagged(served_model):
    cfg, model, params = served_model
    reqs = _requests(cfg, n=2, seed=3, max_new=4)
    eng = ContinuousServer(model, num_slots=2, capacity=CAP,
                           quant="none")
    it = eng.serve(params, reqs)
    first = next(it)                      # pulls only the first token
    assert first.index == 0 and first.uid == reqs[0].uid
    rest = list(it)
    done_uids = {ev.uid for ev in rest + [first] if ev.done}
    assert done_uids == {r.uid for r in reqs}


def test_generate_pins_post_eos_positions(served_model):
    """Regression: rows that stop early must emit ``eos_id`` for every
    later position instead of resampled garbage, and other rows'
    tokens must be unaffected (per-row attention)."""
    cfg, model, params = served_model
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (3, 6)).astype(np.int32)
    srv = Server(model)
    free = srv.generate(params, prompts, max_new=8)
    # choose an eos that row 0 emits early and rows emit at different
    # steps (or never) -> heterogeneous stop pattern
    eos = int(free[0, 1])
    toks = srv.generate(params, prompts, max_new=8, eos_id=eos)
    assert toks.shape[1] == 8 or np.all(toks[:, -1] == eos)
    stopped = [np.argmax(row == eos) if (row == eos).any() else None
               for row in toks]
    assert stopped[0] is not None
    for b, row in enumerate(toks):
        j = stopped[b]
        if j is None:
            assert np.array_equal(row, free[b, :len(row)])
            continue
        assert np.array_equal(row[:j + 1], free[b, :j + 1])
        assert np.all(row[j:] == eos), (b, row)
    # at least two distinct stop behaviours in the batch
    assert len({(-1 if j is None else int(j)) for j in stopped}) >= 2


def test_score_mask_matches_fp64_oracle(served_model):
    cfg, model, params = served_model
    rng = np.random.default_rng(4)
    toks = rng.integers(0, cfg.vocab_size, (3, 10)).astype(np.int32)
    mask = (rng.random((3, 10)) > 0.3).astype(np.float32)
    srv = Server(model)
    got = np.asarray(srv.score(params, toks, mask=mask))

    logits = np.asarray(model.logits(params, {"tokens": jnp.asarray(
        toks)}), np.float64)
    lse = np.log(np.sum(np.exp(
        logits - logits.max(-1, keepdims=True)), -1)) \
        + logits.max(-1, keepdims=True)[..., 0]
    lp = np.take_along_axis(
        logits[:, :-1], toks[:, 1:, None], axis=-1)[..., 0] \
        - lse[:, :-1]
    want = (lp * mask[:, 1:]).sum(-1)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # masked-out positions really are excluded: zeroing them in the
    # oracle changes nothing, scoring without a mask does
    full = np.asarray(srv.score(params, toks))
    assert not np.allclose(got, full)


def test_batched_logprobs_normalises(served_model):
    _, model, params = served_model
    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.standard_normal((2, 3, 64)), jnp.float32)
    toks = jnp.asarray(rng.integers(0, 64, (2, 3)), jnp.int32)
    lp = np.asarray(batched_logprobs(logits, toks))
    ref = jax.nn.log_softmax(logits, axis=-1)
    want = np.take_along_axis(np.asarray(ref), np.asarray(toks)[..., None],
                              axis=-1)[..., 0]
    np.testing.assert_allclose(lp, want, rtol=1e-5, atol=1e-5)


def test_engine_rejects_oversized_and_encdec(served_model):
    cfg, model, params = served_model
    eng = ContinuousServer(model, num_slots=2, capacity=16,
                           quant="none")
    big = [Request(uid=0, prompt=np.zeros(12, np.int32), max_new=8)]
    with pytest.raises(ValueError, match="capacity"):
        list(eng.serve(params, big))
    enc_cfg = registry.get_config("seamless-m4t-large-v2", smoke=True)
    enc_model = model_zoo.build(enc_cfg)
    with pytest.raises(ValueError, match="text decoders"):
        ContinuousServer(enc_model)


def test_continuous_server_warmup_and_background_sweeps(
        served_model, fresh_plan_registry):
    """ISSUE-8 serving lifecycle: warmup pre-resolves the scoring
    plans and pre-compiles prefill at every bucketed prompt length;
    background_sweeps attaches a SweepWorker to the default registry;
    close() (context-manager exit) detaches it deadlock-free."""
    from repro.core import autotune
    cfg, model, params = served_model
    with ContinuousServer(model, num_slots=2, capacity=16,
                          page_size=8, quant="none",
                          background_sweeps=True) as eng:
        assert autotune.default_registry().sweep_worker is eng._sweeper
        out = eng.warmup(params)
        V = cfg.vocab_size
        assert out["scoring_shapes"] == ((1, 1, V), (2, 1, V))
        # pow-2 caps clamped to capacity-1: {1, 2, 4, 8, 15}
        assert out["prefill_compiles"] == 5
        # hot set resolved: warming again causes zero tuning events
        assert eng.warmup()["plans"] == 0
        # a bucketed request stream decodes normally post-warmup
        reqs = [Request(**d) for d in synthetic_requests(
            cfg.vocab_size, n=3, seed=3, min_len=3, max_len=8,
            min_new=2, max_new=4, bucket="pow2")]
        got = eng.generate(params, reqs)
        assert sorted(got) == [0, 1, 2]
        # a sweep that raises is counted, not swallowed
        assert eng._sweeper.submit("no_such_op|8|float32|cpu", dict(
            n=8, dtype=jnp.float32, op="no_such_op"))
        assert eng._sweeper.drain(timeout_s=60.0)
    assert autotune.default_registry().sweep_worker is None
    # the count survives close() (the worker is gone, the count is not)
    assert eng.sweep_failures == 1
    eng.close()    # idempotent after context exit
