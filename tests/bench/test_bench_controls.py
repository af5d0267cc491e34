"""The comparisons that decide ``correct`` fail what they must.

* The control: each configuration's plain reference put in the
  program's place one precision below the configuration's (sums of
  bf16-rounded inputs with f32 accumulation for the f32 reduction, fp8
  matmuls for the bf16 decoder) reads above a limit, where the program
  reads below every limit.
* The faults: a run of the harness, its look for a chip skipped, with
  the timed path broken underneath (an answer or a token altered where
  it is produced) comes out not correct.

At sizes a CPU test run can hold, with limits set by the same rule as
the cells' from readings at these sizes on the CPU, over eight seeds:
``reduce_sum`` of signed data, program 1.3e-6 to 4.6e-6 %, control
2.0e-3 to 5.8e-3 %; ``squared_sum``, which XLA's CPU dot sums less
accurately than the TPU, program 4.3e-4 to 1.05e-3 %, control 9.9e-4
to 1.2e-2 %, so no limit there separates them and the control is
caught by ``reduce_sum``; and the decoder at a width of
256 in place of 4096 has logits a quarter as wide (program 0.0014 to
0.0079, control 0.046 to 0.074 over four seeds).
"""

import glob
import json
import os

import numpy as np
import pytest

from bench import generator, run

SMALL_GAP_LIMIT = 0.02
CPU_SUM_LIMIT = 1e-4
CPU_SQ_LIMIT = 5e-3
SEED = 2**31 + 4099


def _cfg(name):
    with open(os.path.join(run.BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def cpu_reduce():
    return dict(_cfg("reduce-f32"),
                data={"dist": "uniform", "low": -1.0, "high": 1.0},
                limits={"reduce_sum_err_pct": CPU_SUM_LIMIT,
                        "squared_sum_err_pct": CPU_SQ_LIMIT})


def _ref(name):
    return run.load_module(os.path.join(run.BENCH, "configs",
                                        f"{name}.ref.py"),
                           "t_ref_" + name.replace("-", "_"))


REDUCE_MIX = {"kind": "calls", "ops": ["reduce_sum", "squared_sum"],
              "sizes_log2": [12, 16], "arrays_per_size": 2,
              "shuffle": True}


def small_decoder():
    cfg = _cfg("glm4-9b")
    cfg.update(num_layers=2, hidden_size=256, num_attention_heads=4,
               multi_query_group_num=2, kv_channels=64,
               ffn_hidden_size=512, padded_vocab_size=4096,
               limits={"max_logit_gap": SMALL_GAP_LIMIT})
    return cfg


SERVE_MIX = {"kind": "requests", "slots": 4, "capacity": 128,
             "page_size": 16, "prompt_buckets": [16, 32],
             "prompt_weights": [0.5, 0.5], "round": 2, "rounds": 50,
             "max_new": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                         "min": 8, "max": 48},
             "sampling": "greedy", "check": {"tokens": 100,
                                             "max_requests": 4}}


def _run(config, traffic, cfg, mix, seconds=4.0):
    """A run of a cell of ``config`` under ``traffic``, reporting the
    end-to-end metrics BENCHMARK.json gives such a cell."""
    spec = run.load_spec()
    spec["workloads"] = [{"name": "t.cell", "config": config,
                          "traffic": traffic, "chips": 1, "why": "test"}]
    spec["configs"] = [{"name": config,
                        "file": f"bench/configs/{config}.json"}]
    # The metrics of BENCHMARK.json's cells of this config and traffic,
    # or of this config where no cell runs the traffic yet.
    cells = {c["name"] for c in run.load_spec()["workloads"]
             if (c["config"], c["traffic"]) == (config, traffic)} or \
        {c["name"] for c in run.load_spec()["workloads"]
         if c["config"] == config}
    spec["end_to_end"] = [dict(m, workloads=["t.cell"])
                          for m in spec["end_to_end"]
                          if cells & set(m.get("workloads", cells))]
    spec["per_layer"] = []
    return run.run_cell(spec, "t.cell", SEED, seconds, False,
                        require_chip=False, load_config=lambda f: cfg,
                        load_mix=lambda n: mix)


def test_reduce_control_reads_above_the_limit_and_program_below():
    cfg, ref = cpu_reduce(), _ref("reduce-f32")
    driver = run.load_module(os.path.join(run.BENCH, "drivers",
                                          "reduce.py"), "t_drv_reduce")
    r = driver.readings(cfg, REDUCE_MIX, SEED, 0.5, ref)
    limits = cfg["limits"]
    assert r["answers"] >= len(REDUCE_MIX["ops"]) * 4
    assert set(r["program"]) == set(limits)
    assert all(v <= limits[k] for k, v in r["program"].items())
    assert r["program_failed"] == 0
    assert r["control"]["reduce_sum_err_pct"] > limits["reduce_sum_err_pct"]
    assert r["control_failed"] > 0


def test_reduce_run_is_correct_and_an_altered_answer_is_not(monkeypatch):
    out = _run("reduce-f32", "stream", cpu_reduce(), REDUCE_MIX)
    assert out["correct"] and out["failed"] == 0
    from repro.core import reduction
    real = reduction.tc_contract
    monkeypatch.setattr(reduction, "tc_contract",
                        lambda a, b: real(a, b) * (1.0 + 1e-3))  # 0.1%
    out = _run("reduce-f32", "stream", cpu_reduce(), REDUCE_MIX)
    assert not out["correct"] and out["failed"] > 0
    assert list(out)[-1] == "checks"


def test_decoder_control_reads_above_the_limit_and_program_below():
    cfg = small_decoder()
    driver = run.load_module(os.path.join(run.BENCH, "drivers",
                                          "serve.py"), "t_drv_serve")
    r = driver.readings(cfg, SERVE_MIX, SEED, 3.0, _ref("glm4-9b"))
    assert r["answers"] >= 50
    assert r["program"]["max_logit_gap"] <= SMALL_GAP_LIMIT \
        < r["control"]["max_logit_gap"]


REQUEST_MIXES = sorted(
    n for n in (os.path.basename(p)[:-len(".json")] for p in glob.glob(
        os.path.join(run.BENCH, "traffic", "*.json")))
    if generator.load_mix(n)["kind"] == "requests")


@pytest.mark.parametrize("traffic", REQUEST_MIXES)
def test_serve_run_is_correct_and_an_altered_token_is_not(monkeypatch,
                                                          traffic):
    # The small mix with as many slots in use as the traffic's own.
    mix = dict(SERVE_MIX, slots=generator.load_mix(traffic)["slots"])
    out = _run("glm4-9b", traffic, small_decoder(), mix)
    assert out["correct"], out["checks"]
    from repro.launch import serve
    real = serve.ContinuousServer._pick

    def altered(self, row_logits, uid, index):
        tok = real(self, row_logits, uid, index)
        return (tok + 1) % self.cfg.vocab_size if index == 3 else tok

    monkeypatch.setattr(serve.ContinuousServer, "_pick", altered)
    out = _run("glm4-9b", traffic, small_decoder(), mix)
    assert not out["correct"] and out["failed"] > 0


def test_a_request_in_flight_at_the_close_is_served_to_its_end():
    import time
    from types import SimpleNamespace as Ev
    driver = run.load_module(os.path.join(run.BENCH, "drivers",
                                          "serve.py"), "t_drv_drain")
    # Request 1 had tokens 0-1 in the window; 2-4 come after the close.
    after = [Ev(uid=1, index=i, token=10 + i, done=i == 4)
             for i in (2, 3, 4)] + [Ev(uid=2, index=0, token=7,
                                       done=False)] * 5
    want = {"max_requests": 1, "tokens": 5}
    served, finished = {1: [10, 11]}, set()
    gen = iter(after)
    driver._drain(gen, served, finished, want, time.perf_counter())
    assert finished == {1} and served[1] == [10, 11, 12, 13, 14]
    assert next(gen).uid == 2          # nothing served past the need
    # Past its time the drain serves nothing more.
    served, finished = {1: [10, 11]}, set()
    driver._drain(iter(after), served, finished, want,
                  time.perf_counter() - driver.DRAIN_S)
    assert served == {1: [10, 11]} and not finished


def test_the_token_that_closes_the_window_counts_in_it():
    import time
    from types import SimpleNamespace as Ev
    driver = run.load_module(os.path.join(run.BENCH, "drivers",
                                          "serve.py"), "t_drv_close")

    class Server:
        def serve(self, params, queue):
            # Request 1 admitted and decoded once, then request 2,
            # whose admission (a long prefill) straddles the window's
            # length; request 1 ends after the close.
            for ev, wait in ((Ev(uid=1, index=0, token=5, done=False), 0),
                             (Ev(uid=1, index=1, token=7, done=False), 0),
                             (Ev(uid=2, index=0, token=6, done=False),
                              0.2),
                             (Ev(uid=1, index=2, token=9, done=True), 0)):
                time.sleep(wait)
                yield ev
            while True:
                yield Ev(uid=3, index=0, token=8, done=True)

    queue = [{"uid": u, "prompt": np.zeros(n, np.int32)}
             for u, n in ((1, 10), (2, 40), (3, 1))]
    state = driver.State(cfg={}, mix={"check": {"max_requests": 1,
                                                "tokens": 2}},
                         seed=0, dec=None, server=Server(), params=None,
                         queue=queue)
    rec = driver.window(state, 0.1)
    assert rec.tokens == {1: [5, 7], 2: [6]} and rec.window_s >= 0.2
    e2e = driver.end_to_end(rec)
    assert e2e["prompt_tok_s"] == pytest.approx(50 / rec.window_s)
    assert e2e["output_tok_s"] == pytest.approx(3 / rec.window_s)
    assert rec.served[1] == [5, 7, 9] and rec.finished == {1}
