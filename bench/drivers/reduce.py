"""Driver of the reduction cells.

The system under test is the paper's primitive through its public
entry, ``repro.core.integration.reduce_sum`` / ``squared_sum``, called
with the library defaults (``method="mma"``, ``chain=4``) from Python,
as an integrator calls it: each call goes through the op registry and
the engine it picks, and each result is read back to the host.  The
arrays are resident on the device, made from the seed in one jitted
call; the window cycles through the mix's rounds of calls.
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import numpy as np

from bench import flops, generator
from bench import weights as W


@dataclasses.dataclass
class State:
    cfg: dict
    mix: dict
    seed: int
    arrays: dict          # array_id -> device array
    sizes: dict           # array_id -> n
    ops: dict             # op name -> callable


@dataclasses.dataclass
class Record:
    t_open: float
    t_close: float
    calls: list           # (op, array_id, t0, t1, t2, value)
    spans: list           # (name, t0, t1), perf_counter seconds
    sizes: dict

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open


def make_arrays(cfg: dict, mix: dict, seed: int) -> dict:
    """The mix's resident arrays, uniform in [low, high), in one jitted
    call from the seed."""
    spec = cfg["data"]
    lo, hi = float(spec["low"]), float(spec["high"])
    layout = generator.arrays(mix)

    def make(words):
        out = {}
        for aid, n in layout:
            u = W.uniform(W.tensor_key(words, "data", aid),
                          W.flat_index((n,)))
            out[aid] = (lo + (hi - lo) * (u + 1.0) / 2.0).astype(
                cfg["dtype"])
        return out

    return jax.jit(make)(W.seed_words(seed))


def setup(cfg: dict, mix: dict, seed: int) -> State:
    from repro.core import integration as ci
    arrays = make_arrays(cfg, mix, seed)
    ops = {"reduce_sum": ci.reduce_sum, "squared_sum": ci.squared_sum}
    state = State(cfg, mix, seed, arrays, dict(generator.arrays(mix)),
                  {op: ops[op] for op in mix["ops"]})
    # Warm every (op, shape) the window calls: the eager programs the
    # registry dispatches compile (or load from the cache) here.
    for op, aid in generator.calls(mix, seed, 0):
        for _ in range(2):
            float(state.ops[op](state.arrays[aid]))
    return state


def window(state: State, seconds: float) -> Record:
    calls, spans = [], []
    rnd = 0
    t_open = time.perf_counter()
    t_end = t_open + seconds
    while True:
        for op, aid in generator.calls(state.mix, state.seed, rnd):
            fn, x = state.ops[op], state.arrays[aid]
            t0 = time.perf_counter()
            r = fn(x)
            t1 = time.perf_counter()
            v = float(r)
            t2 = time.perf_counter()
            calls.append((op, aid, t0, t1, t2, v))
            spans.append(("bench.dispatch", t0, t1))
            spans.append(("bench.sync", t1, t2))
            if t2 >= t_end:
                return Record(t_open, t2, calls, spans, state.sizes)
        rnd += 1


def end_to_end(rec: Record) -> dict:
    nbytes = sum(flops.reduce_bytes(rec.sizes[aid])
                 for _, aid, *_ in rec.calls)
    return {"prim_GBps": nbytes / rec.window_s / 1e9}


def work(rec: Record) -> dict:
    """What the window's calls had to do, for the per-layer readers."""
    return {"bytes": sum(flops.reduce_bytes(rec.sizes[a])
                         for _, a, *_ in rec.calls),
            "flops": sum(flops.reduce_flops(rec.sizes[a])
                         for _, a, *_ in rec.calls),
            "dispatch_s": [t1 - t0 for _, _, t0, t1, _, _ in rec.calls]}


def release(state: State) -> dict:
    """Bring the data to the host for the reference; free the device."""
    host = {aid: np.asarray(x) for aid, x in state.arrays.items()}
    state.arrays.clear()
    return host


def check(rec: Record, cfg: dict, mix: dict, seed: int, ref,
          host: dict) -> tuple:
    """Every answer of the window against the float64 reference: for
    each op, the widest error of its answers as a share of the sum of
    their terms' magnitudes, against the op's own limit.  Returns
    ``(checks, failed)``."""
    want = {(op, aid): ref.reference(op, host[aid])
            for op, aid in {(op, aid) for op, aid, *_ in rec.calls}}
    worst, failed = {}, 0
    for op, aid, _, _, _, v in rec.calls:
        e = ref.err_pct(v, want[(op, aid)])
        worst[op] = max(worst.get(op, 0.0), e)
        failed += not e <= float(cfg["limits"][f"{op}_err_pct"])
    return ({f"{op}_err_pct": (e, float(cfg["limits"][f"{op}_err_pct"]))
             for op, e in sorted(worst.items())}, failed)


def readings(cfg: dict, mix: dict, seed: int, seconds: float,
             ref) -> dict:
    """The numbers ``check`` compares, for a window of the program and
    one of the control (the plain reference one precision below,
    ``ref.control``) put in the program's place, on the same data."""
    state = setup(cfg, mix, seed)
    prog = window(state, seconds)
    state.ops = {op: functools.partial(ref.control, op)
                 for op in state.ops}
    for op, aid in generator.calls(mix, seed, 0):
        float(state.ops[op](state.arrays[aid]))
    ctrl = window(state, seconds)
    host = release(state)
    out = {"answers": len(prog.calls)}
    for side, rec in (("program", prog), ("control", ctrl)):
        checks, failed = check(rec, cfg, mix, seed, ref, host)
        out[side] = {k: v for k, (v, _) in checks.items()}
        out[side + "_failed"] = failed
    return out


def attempted(rec: Record) -> int:
    return len(rec.calls)
