#!/usr/bin/env python3
"""Bring-up smoke test: drive the main path once on a TPU and check it.

One chip (the default):

* primitive -- ``reduce_sum`` and ``squared_sum`` on the ``pallas``,
  ``pallas_ec``, ``pallas_dd``, ``mma`` and ``auto`` engines, ``cumsum``
  and ``segment_sum`` on their Pallas kernels, and ``ops.mma_reduce``'s
  recurrence variant, at n = 2^24 and 2^28 float32 drawn from the seed.
  Each result is checked against a float64 NumPy oracle, and every
  Pallas call must compile to a ``tpu_custom_call``.
* serving -- gemma2-2b at its published widths (26 layers, d 2304,
  vocab 256000; random weights from the seed) serves 8 requests through
  ``ContinuousServer`` (4 slots, capacity 1024), once with the default
  engines and once with ``fused_pallas`` attention.  Each stream must
  match ``Server.generate`` run one request at a time on the same
  engines, and the two attention engines' prefill logits must agree
  within ``LOGIT_RTOL``.

Four chips (``--chips 4``), and nothing else:

* mesh -- ``tc_psum`` and ``tc_global_norm`` over a 4-device mesh
  against the float64 oracle, then 3 steps of ``launch.train.run`` on
  gemma2-2b at its published widths (batch and sequence cut) on a
  1 x 4 (data x model) mesh.  The losses must be finite and the
  trainer's gradient norm must match a plain ``jnp`` norm of the same
  gradients.

Each phase prints one line.  The last line of stdout is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``.  The
script exits non-zero, printing no JSON line, when JAX finds no TPU,
when the package is not beside it, or when a phase fails.  Times are
bring-up observations, not benchmarks.

Usage:  python3 chip_smoke.py [--chips 4] [--seed N]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SIZES = (1 << 24, 1 << 28)
NUM_SEGMENTS = 64
PROMPT_LENS = (128, 256, 384, 512)   # four lengths: four prefill shapes
NUM_REQUESTS = 8
MAX_NEW = 32
NUM_SLOTS = 4
CAPACITY = 1024
# The two attention engines round differently (the default one feeds
# bf16 probabilities to the value matmul, the fused kernel keeps f32),
# and 26 layers of bf16 activations compound it: a 26-layer narrow
# gemma2 differs by ~2% of its largest logit on the CPU.  A broken
# kernel moves logits by their whole scale.
LOGIT_RTOL = 0.1
# The plain MMA engines' modelled error (docs/precision.md: ~0.2% under
# bf16 multiplicand truncation) bounds every f32 engine; the mesh phase
# holds its collectives to it.
MMA_PCT = 0.2
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 256, 3


def pct_err(got, want):
    return 100.0 * abs(float(got) - float(want)) / max(abs(float(want)),
                                                       1e-300)


class Phase:
    """Times one phase and prints its line: wall seconds, the seconds
    spent compiling (``jit_stats["compile_s"]``, which main()'s JAX
    monitoring listener sums, cache reads included), and the device's
    peak bytes in use so far.  A failed check is recorded and the phase
    goes on, so that one run reports every failure."""

    def __init__(self, name, devices, jit_stats):
        self.name, self.devices, self.jit_stats = name, devices, jit_stats
        self.notes = []
        self.failures = []

    def check(self, cond, msg):
        if not cond:
            self.failures.append(msg)

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.jit_stats["compile_s"]
        return self

    def __exit__(self, typ, exc, tb):
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.devices)
        if typ is not None:
            self.failures.append(f"{typ.__name__}: {exc}")
        status = "FAIL: " + "; ".join(self.failures) \
            if self.failures else "ok"
        print(f"phase {self.name}: {status}; "
              f"{time.perf_counter() - self.t0:.1f}s wall, "
              f"{self.jit_stats['compile_s'] - self.c0:.1f}s compiling, "
              f"peak {peak / 2**30:.2f} GiB", flush=True)
        for note in self.notes:
            print(f"  {note}", flush=True)
        return False


def timed(fn, *args):
    jax.block_until_ready(fn(*args))        # warm
    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t


# ------------------------------------------------------------ primitive


def phase_primitive(ph, *, sizes, seed):
    from repro.core import autotune
    from repro.core import integration as ci
    from repro.core.precision import F64_EQUIVALENT, dd_value
    from repro.kernels import ops

    def run(label, fn, x, want, tol, *, kind="pct", pallas=True,
            extra=()):
        try:
            c = jax.jit(fn).lower(x, *extra).compile()
            got, secs = timed(c, x, *extra)
        except Exception as e:              # record it, go on
            ph.check(False, f"{label}: {type(e).__name__}: {e}")
            return
        has_kernel = "tpu_custom_call" in c.as_text()
        if pallas:
            ph.check(has_kernel, f"{label}: no tpu_custom_call")
        if kind == "pct":
            err = pct_err(dd_value(got), want)
        else:                     # % of the largest value, largest error
            got = np.asarray(got, np.float64)
            err = float(100.0 * np.max(np.abs(got - want))
                        / np.max(np.abs(want)))
        ph.notes.append(f"{label}: err {err:.3e}% (tol {tol:.1e}%), "
                        f"{secs * 1e3:.2f} ms, kernel {has_kernel}")
        ph.check(np.isfinite(err) and err <= tol,
                 f"{label}: error {err:.3e} over {tol:.1e}")

    for n in sizes:
        key = jax.random.PRNGKey(seed + n)
        kx, ki = jax.random.split(key)
        x = jax.random.uniform(kx, (n,), jnp.float32)
        xh = np.asarray(x, np.float64)
        tag = f"n=2^{n.bit_length() - 1}"
        sums = {"reduce_sum": xh.sum(), "squared_sum": (xh * xh).sum()}
        for op, hook in (("reduce_sum", ci.reduce_sum),
                         ("squared_sum", ci.squared_sum)):
            for m in ("pallas", "pallas_ec", "pallas_dd", "mma", "auto"):
                pol = F64_EQUIVALENT if m == "pallas_dd" else None
                fn = (lambda a, hook=hook, m=m, pol=pol:
                      hook(a, method=m, precision=pol))
                if m == "auto":             # the plan dispatch resolves
                    plan = autotune.get_plan(n, jnp.float32, op=op)
                    label = f"{op}/auto->{plan.method} {tag}"
                else:
                    plan = autotune.ReductionPlan(method=m)
                    label = f"{op}/{m} {tag}"
                tol = autotune.model_percent_error(plan, n, jnp.float32,
                                                   op=op)
                run(label, fn, x, sums[op], tol,
                    pallas=plan.method.startswith("pallas"))
        pallas = autotune.ReductionPlan(method="pallas")
        run(f"reduce_sum/recurrence {tag}",
            lambda a: ops.mma_reduce(a, variant="recurrence"), x,
            sums["reduce_sum"],
            autotune.model_percent_error(pallas, n, jnp.float32))
        run(f"cumsum/pallas {tag}",
            lambda a: ci.cumsum(a, method="pallas"), x, np.cumsum(xh),
            autotune.model_percent_error(pallas, n, jnp.float32, op="scan"),
            kind="max")
        ids = jax.random.randint(ki, (n,), 0, NUM_SEGMENTS, jnp.int32)
        want = np.bincount(np.asarray(ids), weights=xh,
                           minlength=NUM_SEGMENTS)
        run(f"segment_sum/pallas S={NUM_SEGMENTS} {tag}",
            lambda a, i: ci.segment_sum(a, i, NUM_SEGMENTS,
                                        method="pallas"), x, want,
            autotune.model_percent_error(pallas, n, jnp.float32,
                                         op="segment_sum"),
            kind="max", extra=(ids,))
        del x, ids, xh


# -------------------------------------------------------------- serving


def serving_config():
    """gemma2-2b at its published widths, weights stored in bf16: f32
    weights (9.74 GiB) make the compiled decode step need 14.33 GiB
    (XLA converts every stacked weight to bf16 inside the step), which
    leaves too little of the chip's 16 GiB for the paged store, its
    dense view and the reference server's caches."""
    from repro.configs import gemma2_2b
    return dataclasses.replace(gemma2_2b.FULL, param_dtype=jnp.bfloat16)


def serve_and_compare(ph, model, params, reqs, attn, last_logits, *,
                      max_new, num_slots, capacity):
    """Serve ``reqs`` through ContinuousServer on the ``attn`` engine,
    then check each stream against Server.generate, one request at a
    time on the same engines and cache capacity (one Server per prompt
    length); keep each request's last prefill logits."""
    from repro.launch.serve import ContinuousServer, Server

    name = attn or "default"
    eng = ContinuousServer(model, num_slots=num_slots, capacity=capacity,
                           quant="none", attn_method=attn)
    t = time.perf_counter()
    with eng:
        outs = eng.generate(params, reqs)
    secs = time.perf_counter() - t
    ph.check(eng.sweep_failures == 0,
             f"{name}: {eng.sweep_failures} failed sweeps")
    ph.check(sorted(outs) == [r.uid for r in reqs],
             f"{name}: served {sorted(outs)}")
    ntok = sum(len(v) for v in outs.values())
    servers = {}
    mismatched = []
    t = time.perf_counter()
    for r in reqs:
        L = len(r.prompt)
        srv = servers.setdefault(
            L, Server(eng.model, extra_capacity=capacity - L))
        ref = srv.generate(params, r.prompt[None], max_new=max_new)[0]
        if not np.array_equal(outs[r.uid], ref):
            first = int(np.argmax(outs[r.uid] != ref)) \
                if len(ref) == len(outs[r.uid]) else -1
            mismatched.append((r.uid, first))
        logits, _ = srv._prefill(params, {"tokens": r.prompt[None]})
        last_logits.setdefault(r.uid, {})[name] = np.asarray(
            logits[0, -1], np.float32)
    ref_secs = time.perf_counter() - t
    ph.notes.append(
        f"{name}: {ntok} tokens from {len(reqs)} requests in {secs:.2f}s "
        f"through ContinuousServer (compiles included); reference "
        f"Server.generate {ref_secs:.2f}s; streams that differ "
        f"(uid, first index): {mismatched}")
    ph.check(not mismatched, f"{name}: token streams differ from "
                             f"Server.generate: {mismatched}")


def phase_serving(ph, *, cfg, seed, prompt_lens=PROMPT_LENS,
                  num_requests=NUM_REQUESTS, max_new=MAX_NEW,
                  num_slots=NUM_SLOTS, capacity=CAPACITY):
    from repro.launch.serve import Request
    from repro.models import model_zoo

    model = model_zoo.build(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    lens = rng.choice(prompt_lens, size=num_requests)
    reqs = [Request(uid=i, prompt=rng.integers(
                0, cfg.vocab_size, int(L)).astype(np.int32),
                max_new=max_new) for i, L in enumerate(lens)]
    last_logits = {}
    for attn in (None, "fused_pallas"):
        try:
            serve_and_compare(ph, model, params, reqs, attn, last_logits,
                              max_new=max_new, num_slots=num_slots,
                              capacity=capacity)
        except Exception as e:              # record it, try the next
            ph.check(False, f"{attn or 'default'}: "
                            f"{type(e).__name__}: {e}")
    worst = 0.0
    for uid, by in last_logits.items():
        if len(by) < 2:
            continue
        a, b = by["default"], by["fused_pallas"]
        ph.check(np.all(np.isfinite(a)) and np.all(np.isfinite(b)),
                 f"uid {uid}: non-finite prefill logits")
        worst = max(worst, float(np.max(np.abs(a - b))
                                 / max(np.max(np.abs(a)), 1e-30)))
    ph.notes.append(f"prefill logits, fused_pallas vs default: largest "
                    f"difference {worst:.3e} of the largest logit "
                    f"(tol {LOGIT_RTOL})")
    ph.check(worst <= LOGIT_RTOL, f"prefill logits differ by {worst:.3e}")


# ----------------------------------------------------------------- mesh


def phase_mesh(ph, *, arch="gemma2-2b", smoke=False, n=1 << 24,
               batch=TRAIN_BATCH, seq=TRAIN_SEQ, steps=TRAIN_STEPS,
               seed=0):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import registry
    from repro.configs.base import SHAPES
    from repro.data.pipeline import SyntheticLMData
    from repro.distributed import sharding as shd
    from repro.distributed import tc_collectives as tcc
    from repro.launch import train
    from repro.launch.mesh import make_local_mesh
    from repro.models import model_zoo

    ndev = len(jax.devices())
    mesh = make_local_mesh(ndev, 1)
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.device_put(jax.random.uniform(kx, (n,), jnp.float32),
                       NamedSharding(mesh, P("data")))
    y = jax.device_put(
        jax.random.normal(ky, (4 * ndev, n // (4 * ndev)), jnp.float32),
        NamedSharding(mesh, P("data", None)))
    xh, yh = np.asarray(x, np.float64), np.asarray(y, np.float64)
    got = tcc.tc_psum(x, mesh=mesh)
    err = pct_err(got, xh.sum())
    ph.notes.append(f"tc_psum n=2^{n.bit_length() - 1} over {ndev} "
                    f"devices: err {err:.3e}% (tol {MMA_PCT}%)")
    ph.check(err <= MMA_PCT, f"tc_psum error {err:.3e}%")
    got = tcc.tc_global_norm({"x": x, "y": y}, mesh=mesh)
    err = pct_err(got, math.sqrt((xh * xh).sum() + (yh * yh).sum()))
    ph.notes.append(f"tc_global_norm over {ndev} devices: err "
                    f"{err:.3e}% (tol {MMA_PCT}%)")
    ph.check(err <= MMA_PCT, f"tc_global_norm error {err:.3e}%")
    del x, y

    state, history = train.run(
        arch, steps=steps, smoke=smoke, batch_override=batch,
        seq_override=seq, data_parallel=1, model_parallel=ndev,
        log_every=1, seed=seed)
    losses = [loss for _, loss in history]
    ph.notes.append(f"train {arch} batch {batch} seq {seq} on a 1x{ndev} "
                    f"mesh: losses {losses}")
    ph.check(len(losses) == steps and all(np.isfinite(losses)),
             f"losses {losses}")

    # The trainer's gradient norm (adamw.clip_by_global_norm) against a
    # plain jnp norm of the same gradients, at the trained parameters.
    params = state.params
    del state
    cfg = registry.get_config(arch, smoke=smoke)
    model = model_zoo.build(cfg)
    tmesh = make_local_mesh(1, ndev)
    shape_cfg = dataclasses.replace(SHAPES["train_4k"], global_batch=batch,
                                    seq_len=seq)
    data = SyntheticLMData(cfg, shape_cfg, seed=seed,
                           sharding=NamedSharding(tmesh, P(("data",))))
    b = data.batch_at(steps)

    @jax.jit
    def norms(p, bt):
        with shd.axis_rules(tmesh):
            grads = jax.grad(lambda q: model.loss(q, bt)[0])(p)
            ours = tcc.tc_global_norm(grads, method=cfg.reduce_method,
                                      via="gspmd")
        plain = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree_util.tree_leaves(grads)))
        return ours, plain

    c = norms.lower(params, b).compile()
    ours, plain = (float(v) for v in c(params, b))
    err = pct_err(ours, plain)
    ph.notes.append(f"grad_norm {ours:.6g} vs plain jnp {plain:.6g}: "
                    f"err {err:.3e}% (tol {MMA_PCT}%)")
    ph.check(np.isfinite(ours) and err <= MMA_PCT,
             f"grad_norm {ours} vs {plain}")


# ----------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    cache_dir = use_compile_cache()
    from jax import monitoring

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    jit_stats = {"hits": 0, "misses": 0, "compile_s": 0.0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            jit_stats["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            jit_stats["misses"] += 1

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            jit_stats["compile_s"] += secs

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    print(f"device {devices[0].device_kind} x{len(devices)}, "
          f"compile cache {cache_dir}", flush=True)
    if args.chips == 4:
        phases = [("mesh", lambda ph: phase_mesh(ph, seed=args.seed))]
    else:
        phases = [("primitive", lambda ph: phase_primitive(
                       ph, sizes=SIZES, seed=args.seed)),
                  ("serving", lambda ph: phase_serving(
                      ph, cfg=serving_config(), seed=args.seed))]
    failed = []
    for name, body in phases:
        ph = Phase(name, devices, jit_stats)
        try:
            with ph:
                body(ph)
        except Exception:                   # printed; fail the run
            import traceback
            traceback.print_exc()
        if ph.failures:
            failed.append(name)
    print(f"compile cache: {jit_stats['hits']} hits, "
          f"{jit_stats['misses']} misses", flush=True)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
