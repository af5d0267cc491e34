"""The harness (bench/run.py): it finds every part of a cell by name,
keeps BENCHMARK.json inside its contract, and refuses to measure
without a TPU."""

import glob
import json
import os
import re
import subprocess
import sys

import pytest

from bench import peaks, run, trace

ROOT = run.ROOT
SPEC = run.load_spec()
CELLS = [c["name"] for c in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_parts_are_found_by_name(cell):
    parts = run.cell_parts(SPEC, cell)
    cfg_file = os.path.join(ROOT, parts["config"]["file"])
    with open(cfg_file) as f:
        cfg = json.load(f)
    assert cfg["name"] == parts["config"]["name"]
    assert os.path.exists(cfg_file[:-len(".json")] + ".ref.py")
    assert os.path.exists(os.path.join(run.BENCH, "drivers",
                                       f"{cfg['driver']}.py"))
    assert os.path.exists(os.path.join(run.BENCH, "traffic",
                                       f"{parts['cell']['traffic']}.json"))
    for m in parts["per_layer"]:
        assert os.path.exists(os.path.join(run.BENCH, "metrics",
                                           f"{m['name']}.py"))
    names = {m["name"] for m in parts["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert parts["per_layer"]


def test_cells_report_their_metrics():
    e2e = {c: {m["name"] for m in run.cell_parts(SPEC, c)["end_to_end"]}
           for c in CELLS}
    assert e2e["reduce-f32.stream"] == {"prim_GBps", "setup_s"}
    assert e2e["glm4-9b.prefill-heavy"] == {"prompt_tok_s", "itl_p95_ms",
                                            "setup_s"}
    for cell in CELLS:
        for m in run.cell_parts(SPEC, cell)["per_layer"]:
            assert m["moves"] in e2e[cell]


def test_an_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        run.cell_parts(SPEC, "no-such.cell")


def test_benchmark_json_keeps_its_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert 2 + 14 * 24 * (SPEC["run_seconds"] + 60) \
        + 24 * 180 + 1200 <= 43200
    for p in SPEC["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    used = {c["config"] for c in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
    pairs = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25 and m["better"] in (
            "lower", "higher")
    for m in SPEC["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", CELLS)
        if m["unit"] == "%" and "roofline" in m["name"]:
            assert m["name"].endswith("_roofline")
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_peaks_know_v5e_and_refuse_the_unknown():
    p = peaks.peak("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu")


def test_the_harness_refuses_a_cpu_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "reduce-f32.stream", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == run.NO_CHIP
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


READERS = sorted(os.path.basename(p)[:-len(".py")] for p in glob.glob(
    os.path.join(run.BENCH, "metrics", "*.py")))


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_without_a_trace(name):
    ctx = {"trace": None, "work": {}, "compiles": [], "peaks": {}}
    reader = run.load_module(os.path.join(run.BENCH, "metrics",
                                          f"{name}.py"),
                             "t_" + name.replace(".", "_"))
    v = reader.read(ctx)
    assert v is None or name == "serve.compiles_in_window"


def test_roofline_and_mfu_stay_at_or_under_100_when_work_fits_the_time():
    ctx = {"trace": {"busy_s": 1.0, "window_s": 2.0},
           "work": {"bytes": 819e9, "flops": 1e9},
           "peaks": peaks.peak("TPU v5 lite"), "compiles": []}
    roof = run.load_module(os.path.join(run.BENCH, "metrics",
                                        "prim.reduce_roofline.py"), "t_r")
    assert roof.read(ctx) == pytest.approx(100.0)
    idle = run.load_module(os.path.join(run.BENCH, "metrics",
                                        "prim.device_idle_pct.py"), "t_i")
    assert idle.read(ctx) == pytest.approx(50.0)
    mfu = run.load_module(os.path.join(run.BENCH, "metrics",
                                       "decode.mfu_pct.py"), "t_m")
    ctx["work"] = {"flops": 197e12}
    assert mfu.read(ctx) == pytest.approx(50.0)
    # Two decode programs of 1 s and 3 s (median 2 s); two steps that
    # must each read 819 GB (1 s at peak) and do 98.5 TFLOP (0.5 s).
    plane = "/device:TPU:0"
    ctx["trace"].update(plane=plane, lo=0.0, hi=1e10, events=[
        trace.Event(plane, trace.MODULES_LINE, "jit_decode(7)", s, e)
        for s, e in ((0.0, 1e9), (2e9, 5e9))])
    ctx["work"] = {"decode_steps": 2, "decode_bytes": 2 * 819e9,
                   "decode_flops": 197e12}
    step = run.load_module(os.path.join(run.BENCH, "metrics",
                                        "decode.step_roofline.py"), "t_s")
    assert step.read(ctx) == pytest.approx(50.0)
    ctx["trace"]["events"] = ctx["trace"]["events"][:1]
    assert step.read(ctx) == pytest.approx(100.0)


def test_decode_gaps_leave_out_those_with_an_admission():
    # Decode steps end at 1, 3, 6 and 10 s; the next ones start 0.5,
    # 2 and 0.25 s later, and an admission prefill runs in the second
    # gap: the gaps read are 0.5 and 0.25 s.
    plane = "/device:TPU:0"
    ev = [trace.Event(plane, trace.MODULES_LINE, "jit_decode(7)", s, e)
          for s, e in ((0.0, 1e9), (1.5e9, 3e9), (5e9, 6e9),
                       (6.25e9, 10e9))]
    ev.append(trace.Event(plane, trace.MODULES_LINE, "jit_prefill(3)",
                          3.5e9, 4.5e9))
    ctx = {"trace": {"events": ev, "plane": plane, "lo": 0.0,
                     "hi": 1e10}}
    gap = run.load_module(os.path.join(run.BENCH, "metrics",
                                       "prefill.decode_gap_ms.py"), "t_g")
    assert gap.read(ctx) == pytest.approx(375.0)
    ctx["trace"]["events"] = ev[2:3] + ev[4:]
    assert gap.read(ctx) is None


def test_a_configuration_without_limits_is_refused():
    spec = dict(SPEC, workloads=[{"name": "t.cell", "config": "glm4-9b",
                                  "traffic": "decode-heavy", "chips": 1,
                                  "why": "test"}],
                configs=[{"name": "glm4-9b",
                          "file": "bench/configs/glm4-9b.json"}])

    def without_limits(path):
        with open(path) as f:
            cfg = json.load(f)
        cfg.pop("limits", None)
        return cfg

    with pytest.raises(ValueError, match="no limits"):
        run.run_cell(spec, "t.cell", 1, 1.0, False, require_chip=False,
                     load_config=without_limits)
