"""The readers of the program's spans (bench/program_spans.py and the
prim.* metrics that use it) on a synthetic traced window with a
known offset between the host's clock and the trace's."""

import os
import sys

import pytest

from bench import program_spans as P
from bench import run
from bench import trace as T
from repro import obs

PLANE = "/device:TPU:0"
T_OPEN = 1000.0                 # s, host clock (perf_counter)
HOST0 = 10 ** 12                # ns, the same instant
LO, HI = 5_000_000.0, 6_000_000.0   # ns, the window on the trace clock
READERS = ("prim.registry_us", "prim.enqueue_us",
           "prim.call_idle_us.reduce_sum", "prim.call_idle_us.squared_sum")


def reader(name):
    return run.load_module(os.path.join(run.BENCH, "metrics",
                                        f"{name}.py"),
                           "t_obs_" + name.replace(".", "_"))


def record(hi=HI):
    rec = run.load_module(os.path.join(run.BENCH, "drivers", "reduce.py"),
                          "t_obs_reduce_driver").Record
    return rec(T_OPEN, T_OPEN + (hi - LO) * 1e-9, [], [], {})


def trace(events=None, hi=HI):
    if events is None:
        events = [
            T.Event(PLANE, T.MODULES_LINE, "jit_dot_general(1)",
                    5_045_000, 5_200_000),
            T.Event(PLANE, T.OPS_LINE, "%fusion = f32[] x",
                    5_050_000, 5_200_000),
            T.Event(PLANE, T.OPS_LINE, "%fusion = f32[] x",
                    5_540_000, 5_700_000),
        ]
    busy = sum(e.end - e.start for e in events if e.line == T.OPS_LINE)
    return {"events": events, "planes": [PLANE], "plane": PLANE,
            "lo": LO, "hi": hi, "busy_s": busy * 1e-9,
            "window_s": (hi - LO) * 1e-9}


@pytest.fixture()
def ctx(monkeypatch):
    """Two calls in the window and one before it, recorded through
    repro.obs on a host clock that reads HOST0 at the window's open.
    On the trace's clock (offset LO - HOST0): call A's registry span
    5_010_000..5_110_000 with its engine run 5_030_000..5_100_000;
    call B's 5_500_000..5_560_000 with 5_515_000..5_550_000."""
    ticks = [-50_000, -40_000, -20_000, -10_000,
             10_000, 30_000, 100_000, 110_000,
             500_000, 515_000, 550_000, 560_000]
    clock = iter(HOST0 + t for t in ticks)
    obs.reset()
    monkeypatch.setattr(obs, "_recording", lambda: True)
    monkeypatch.setattr(obs, "_clock", lambda: next(clock))
    for _ in range(3):
        with obs.span("repro.dispatch", op="reduce_sum"):
            with obs.span("repro.engine", engine="mma"):
                pass
    monkeypatch.undo()
    yield {"record": record(), "trace": trace(), "work": {},
           "compiles": [], "peaks": {}}
    obs.reset()


def test_program_spans_move_onto_the_trace_and_keep_the_window(ctx):
    calls = P.spans(ctx, "repro.dispatch")
    runs = P.spans(ctx, "repro.engine")
    assert [(s.t0_ns, s.t1_ns) for s in calls] == [
        (5_010_000, 5_110_000), (5_500_000, 5_560_000)]
    assert [(s.t0_ns, s.t1_ns) for s in runs] == [
        (5_030_000, 5_100_000), (5_515_000, 5_550_000)]
    assert [r.parent_id for r in runs] == [c.id for c in calls]
    assert len(obs.spans()) == 6        # the call before the window
    assert P.spans(ctx, "no.such.span") == []


@pytest.mark.parametrize("name,want", [
    # Self time: A 100 - 70 us, B 60 - 35 us.
    ("prim.registry_us", (30 + 25) / 2),
    # Engine runs: 70 and 35 us.
    ("prim.enqueue_us", (70 + 35) / 2),
    # A (reduce_sum) is the only call with a next one: 490 us from its
    # start to B's, of which its one program ran 155 us.
    ("prim.call_idle_us.reduce_sum", 490 - 155),
    # B, a reduce_sum too, is the window's last call.
    ("prim.call_idle_us.squared_sum", None),
])
def test_readers_give_the_hand_computed_values(ctx, name, want):
    got = reader(name).read(ctx)
    assert got == (want if want is None else pytest.approx(want))


# A stream of calls as the reduction driver makes them, in us from a
# call's start (span repro.dispatch) on the host's clock: the engine
# run starts at 70, and the device runs the call's programs as below;
# the next call starts at the cycle's end.  The device's timeline is
# moved by SKEW from the host's, which the readers must not see.
STREAM = {
    "reduce_sum": ([(100, 110), (400, 2_000), (2_010, 4_850)], 5_020),
    "squared_sum": ([(350, 3_190)], 3_600),
}
# Per call: the cycle less the device time of its programs.
IDLE_US = {"reduce_sum": 5_020 - 10 - 1_600 - 2_840,
           "squared_sum": 3_600 - 2_840}


def stream_ctx(monkeypatch, skew_ns, extra=()):
    ticks, events, t = [], [], 10_000
    for op in ["reduce_sum", "squared_sum"] * 3:
        progs, cycle = STREAM[op]
        ticks += [t, t + 70_000, t + 90_000, t + 95_000]
        events += [T.Event(PLANE, T.MODULES_LINE, "jit_p(1)",
                           LO + t + a * 1e3 + skew_ns,
                           LO + t + b * 1e3 + skew_ns) for a, b in progs]
        t += cycle * 1_000
    events += [T.Event(PLANE, T.MODULES_LINE, "jit_p(1)",
                       LO + a + skew_ns, LO + b + skew_ns)
               for a, b in extra]
    hi = LO + t
    clock = iter(HOST0 + x for x in ticks)
    obs.reset()
    with monkeypatch.context() as m:
        m.setattr(obs, "_recording", lambda: True)
        m.setattr(obs, "_clock", lambda: next(clock))
        for op in ["reduce_sum", "squared_sum"] * 3:
            with obs.span("repro.dispatch", op=op):
                with obs.span("repro.engine", engine="mma"):
                    pass
    return {"record": record(hi), "trace": trace(events, hi)}


@pytest.mark.parametrize("skew_ns", [-1_900_000, -600_000, 0, 800_000])
def test_call_idle_pairs_calls_with_their_programs_under_any_skew(
        monkeypatch, skew_ns):
    ctx = stream_ctx(monkeypatch, skew_ns)
    gaps = P.call_idle(ctx)
    # Every call but the last, each with its own programs.
    assert gaps == {"reduce_sum": [IDLE_US["reduce_sum"] * 1e3] * 3,
                    "squared_sum": [IDLE_US["squared_sum"] * 1e3] * 2}
    for op in STREAM:
        assert reader(f"prim.call_idle_us.{op}").read(ctx) == \
            pytest.approx(IDLE_US[op])
    obs.reset()


def test_call_idle_reads_nothing_when_a_program_fits_no_call(monkeypatch):
    # A program, run for another caller, longer than any call's
    # stretch: no skew fits it in a call.
    ctx = stream_ctx(monkeypatch, -600_000,
                     extra=[(8_000_000, 14_000_000)])
    assert P.call_idle(ctx) == {}
    assert reader("prim.call_idle_us.reduce_sum").read(ctx) is None
    obs.reset()


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_a_trace_or_spans(ctx, name):
    assert reader(name).read(dict(ctx, trace=None)) is None
    obs.reset()
    assert reader(name).read(ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_from_a_program_without_obs(ctx, monkeypatch,
                                                         name):
    import repro
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert reader(name).read(ctx) is None

