#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration in the file the entry names (with its plain reference
beside it, ``<file>.ref.py`` in place of ``.json``), its traffic mix in
``bench/traffic/<traffic>.json``, the driver that runs its kind of
system in ``bench/drivers/<driver>.py`` (the configuration file's
``driver`` key), and each per-layer metric's reader in
``bench/metrics/<metric>.py``.  A new cell, configuration, mix or metric
is new files and entries; nothing here changes.

A run makes its data and weights from ``--seed``, warms every shape the
cell uses (set-up), measures for ``--seconds``, then checks what the
window produced against the plain reference.  With ``--trace 0`` it
reports the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a profiler trace of the window.  The last line of
stdout is one JSON object; the numbers compared, each with its limit,
are the last lines of stderr and the result's last key.  Without a TPU,
or with fewer chips than the cell asks for, it exits 3 and prints no
result.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
NO_CHIP = 3


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell needs."""


def process_age() -> float:
    """Seconds since this process started (Linux; else since import)."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - start / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _T_IMPORT


_T_IMPORT = time.perf_counter()


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_parts(spec: dict, name: str) -> dict:
    """The cell's entry, configuration entry and metric entries."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(cells: {sorted(cells)})")
    cell = cells[name]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if name in m.get("workloads", [name])
             and m["moves"] in reported]
    return {"cell": cell, "config": config, "end_to_end": e2e,
            "per_layer": layer}


def load_cell(parts: dict, load_config=None, load_mix=None) -> tuple:
    """The cell's configuration, traffic mix, driver module and plain
    reference module, each found by name."""
    from bench import generator
    cfg_file = os.path.join(ROOT, parts["config"]["file"])
    cfg = (load_config or _load_json)(cfg_file)
    mix = (load_mix or generator.load_mix)(parts["cell"]["traffic"])
    driver = load_module(os.path.join(BENCH, "drivers",
                                      f"{cfg['driver']}.py"),
                         f"bench_driver_{cfg['driver']}")
    ref = load_module(cfg_file[:-len(".json")] + ".ref.py",
                      "bench_ref_" + cfg["name"].replace("-", "_"))
    return cfg, mix, driver, ref


def use_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, for
    every program however quickly it compiles: only a checkout's first
    run compiles."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def devices(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r}")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
        from bench import peaks
        peaks.peak(devs[0].device_kind)
    return devs


class CompileWatch:
    """Every executable JAX compiles or loads from its cache, with the
    time it was ready (``jax.monitoring``)."""

    def __init__(self):
        from jax import monitoring
        from jax._src import dispatch
        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.seen = []
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == self.event:
            self.seen.append((time.perf_counter(), kw.get("fun_name", "?")))

    def between(self, lo: float, hi: float) -> list:
        return [n for t, n in self.seen if lo <= t <= hi]


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def reduce_trace(log_dir: str, rec, anchor_ns: int, chips: int) -> dict:
    """The traced window in the trace's time base: its events (host
    spans of the driver moved onto it), the device planes used, and
    their mean busy time."""
    from bench import trace as T
    events = T.load(log_dir)
    anchors = [e for e in events if e.name == "bench.anchor"]
    if not anchors:
        raise RuntimeError("the trace holds no bench.anchor span")
    offset = anchors[0].start - anchor_ns
    for name, t0, t1 in rec.spans:
        events.append(T.Event("bench", "spans", name, t0 * 1e9 + offset,
                              t1 * 1e9 + offset))
    lo, hi = rec.t_open * 1e9 + offset, rec.t_close * 1e9 + offset
    planes = T.device_planes(events)[:chips]
    if not planes:
        raise RuntimeError("the trace holds no TPU device plane")
    busy = sum(T.busy_ns(events, p, lo, hi) for p in planes) / len(planes)
    if busy <= 0:
        raise RuntimeError("no operation ran on the device in the window")
    return {"events": events, "planes": planes, "plane": planes[0],
            "lo": lo, "hi": hi, "busy_s": busy * 1e-9,
            "window_s": (hi - lo) * 1e-9}


def breakdown(tr: dict) -> dict:
    from bench import trace as T
    p, lo, hi = tr["plane"], tr["lo"], tr["hi"]
    return {"device_ops": T.top(T.op_totals(tr["events"], p, lo,
                                            hi).items()),
            "idle_gaps": T.top(T.gap_totals(
                T.idle_gaps(tr["events"], p, lo, hi)))}


def run_cell(spec: dict, name: str, seed: int, seconds: float,
             trace: bool, *, require_chip: bool = True,
             load_config=None, load_mix=None) -> dict:
    """One run of cell ``name``; returns the result object.  Tests pass
    ``require_chip=False`` and their own small ``load_config`` /
    ``load_mix`` to drive the rest of a run on the CPU."""
    parts = cell_parts(spec, name)
    cell = parts["cell"]
    devs = devices(int(cell["chips"]), require_chip)
    cfg, mix, driver, ref = load_cell(parts, load_config, load_mix)
    if not cfg.get("limits"):
        raise ValueError(f"configuration {cfg['name']!r} has no limits "
                         f"set from readings (bench/calibrate.py)")
    import jax
    watch = CompileWatch()

    state = driver.setup(cfg, mix, seed)
    setup_s = process_age()
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    anchor_ns = 0
    if trace:
        jax.profiler.start_trace(log_dir,
                                 profiler_options=_profile_options())
        with jax.profiler.TraceAnnotation("bench.anchor"):
            anchor_ns = time.perf_counter_ns()
    try:
        rec = driver.window(state, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    e2e = driver.end_to_end(rec)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:int(cell["chips"])])
    compiles = watch.between(rec.t_open, rec.t_close)
    t_check = time.perf_counter()
    released = driver.release(state)
    del state
    checks, failed = driver.check(rec, cfg, mix, seed, ref, released)
    check_s = time.perf_counter() - t_check

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    out = {"correct": failed == 0, "attempted": driver.attempted(rec),
           "failed": failed}
    if trace:
        try:
            tr = reduce_trace(log_dir, rec, anchor_ns, int(cell["chips"]))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        ctx = {"record": rec, "work": driver.work(rec), "trace": tr,
               "compiles": compiles, "peaks": _peaks(devs[0])}
        metrics = {}
        for m in parts["per_layer"]:
            reader = load_module(os.path.join(BENCH, "metrics",
                                              f"{m['name']}.py"),
                                 "bench_metric_" + m["name"]
                                 .replace(".", "_"))
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        out.update(metrics=metrics, device=device,
                   breakdown=breakdown(tr))
    else:
        e2e["setup_s"] = setup_s
        out.update(metrics={m["name"]: {"value": float(e2e[m["name"]]),
                                        "unit": m["unit"]}
                            for m in parts["end_to_end"]},
                   device=device)
    out["notes"] = {"window_s": rec.window_s, "setup_s": setup_s,
                    "check_s": check_s, "compiles_in_window": compiles}
    out["checks"] = {k: {"value": float(v), "limit": float(lim)}
                     for k, (v, lim) in checks.items()}
    return out


def _peaks(dev) -> dict:
    from bench import peaks
    return peaks.peak(dev.device_kind)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    spec = load_spec()
    use_compile_cache()
    try:
        out = run_cell(spec, args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return NO_CHIP
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
