"""The harness finds configurations, traffic mixes, limits and per-layer
metrics by name, so a later cell needs only new files; and the one
generator gives every seed the same schedule (a warm-up, the window and
a tail) with other token ids."""
import json
import os
import shutil
import subprocess
import sys

import cb_fixtures
import numpy as np
import pytest

from chipbench import generator
from chipbench import harness as H


def test_new_config_mix_and_metric_are_new_files_only(tmp_path):
    base = tmp_path / "chipbench"
    shutil.copytree(H.HERE, base, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    bench = json.load(open(os.path.join(H.ROOT, "BENCHMARK.json")))
    cell = "tiny.bursty"
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "chipbench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": "tiny",
                               "traffic": "bursty", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "sched.steps", "unit": "steps",
                               "better": "lower", "source": "program_counter",
                               "layer": "scheduler", "moves": "ttft_p50_ms",
                               "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (base / "configs" / "tiny.json").write_text(json.dumps(cb_fixtures.TINY))
    mix = dict(json.load(open(base / "traffic" / "chat.json")),
               process="gamma", shape=0.25)
    (base / "traffic" / "bursty.json").write_text(json.dumps(mix))
    (base / "limits" / f"{cell}.json").write_text(
        json.dumps({"limits": {"max_logit_gap": 0.5}}))
    (base / "metrics" / "sched.steps.py").write_text(
        "def read(run):\n    return len(run['steps']) or None\n")

    ctx = H.load_cell(cell, str(base))
    assert ctx["config"]["name"] == "tiny"
    assert ctx["traffic"]["process"] == "gamma"
    assert H.load_limits(cell, str(base)) == {"max_logit_gap": 0.5}
    assert H.load_driver(ctx["traffic"]["kind"], str(base)).Serve
    names = [m["name"] for m in
             H.cell_metrics(ctx["bench"], cell, "per_layer")]
    assert "sched.steps" in names and "chunk_step.mfu" not in names
    got = H.read_per_layer(ctx["bench"], cell, {"steps": [1, 2, 3]},
                           str(base))
    assert got == {"sched.steps": {"value": 3, "unit": "steps"}}
    plan = generator.plan(ctx["traffic"], 100, 10.0, 5)
    assert sum(p.in_window for p in plan) == round(mix["rate_per_s"] * 10)


def test_every_cell_has_its_files():
    bench = H.load_benchmark()
    for cell in bench["workloads"]:
        ctx = H.load_cell(cell["name"])
        assert H.load_limits(cell["name"])
        assert H.load_driver(ctx["traffic"]["kind"])
        for group in ("end_to_end", "per_layer"):
            assert H.cell_metrics(bench, cell["name"], group)
    for m in bench["per_layer"]:
        assert callable(H.load_metric(m["name"]).read)


@pytest.mark.parametrize(
    "cell", [w["name"] for w in H.load_benchmark()["workloads"]])
def test_a_run_without_a_tpu_fails_and_prints_no_result(cell):
    run = subprocess.run(
        [sys.executable, os.path.join(H.HERE, "run.py"), "--workload", cell,
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=H.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert run.returncode != 0
    assert run.stdout == ""
    assert "no TPU" in run.stderr


@pytest.mark.parametrize("process", ["poisson", "gamma"])
def test_seeds_share_the_schedule_and_differ_in_tokens(process):
    mix = dict(H.load_traffic("chat"), process=process, shape=0.5)
    warm = mix["warmup_s"]
    a = generator.plan(mix, 1000, 20.0, 1)
    b = generator.plan(mix, 1000, 20.0, 2**40 + 1)
    for plan in (a, b):
        win = [p for p in plan if p.in_window]
        assert len(win) == round(mix["rate_per_s"] * 20)
        assert all(0 <= p.due_s < 20.0 for p in win)
        early = [p for p in plan if not p.in_window and p.due_s < 0]
        assert len(early) == round(mix["rate_per_s"] * warm)
        assert all(-warm <= p.due_s for p in early)
        assert all(p.due_s > 20.0 for p in plan
                   if not p.in_window and p.due_s >= 0)
        assert [p.due_s for p in plan] == sorted(p.due_s for p in plan)
    sched = lambda plan: [(p.due_s, len(p.prompt), p.max_new, p.in_window)
                          for p in plan]
    assert sched(a) == sched(b)
    assert [p.prompt for p in a] != [p.prompt for p in b]
    lens = [len(p.prompt) for p in a]
    assert lens != sorted(lens)  # arrivals are not in length order
    assert all(mix["prompt"]["min"] <= n <= mix["prompt"]["max"]
               for n in lens)


def test_lognormal_lengths_follow_the_mix():
    spec = {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 32,
            "max": 2048}
    x = generator.lengths(spec, 4000, np.random.default_rng(0))
    assert 480 <= np.median(x) <= 540
    assert x.min() >= 32 and x.max() == 2048
