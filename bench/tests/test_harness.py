"""The harness end to end on the CPU rehearsal, and the contract its files
keep. The rehearsal runs each cell at a tiny size with the library's TPU
decisions and its kernels in interpret mode; its numbers are never
reported under a device metric's name."""
import json
import os
import re
import shutil
import subprocess
import sys
import time

import jax
import pytest

from bench import control, harness

CELLS = ("hpcg104.cg", "yi6b_vocab.tail")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
#: The entries ``yi6b_vocab.tail`` adds to ``BENCHMARK.json`` once its rate
#: and bounds are measured on the chip; until then the tests rehearse it
#: from its files with these entries.
TAIL_ENTRIES = {
    "configs": [{"name": "yi6b_vocab",
                 "file": "bench/configs/yi6b_vocab.json"}],
    "workloads": [{"name": "yi6b_vocab.tail", "config": "yi6b_vocab",
                   "traffic": "tail", "chips": 1}],
    "end_to_end": [
        {"name": n, "unit": "ms", "workloads": ["yi6b_vocab.tail"]}
        for n in ("p50_ms", "p95_ms")],
    "per_layer": [
        {"name": n, "unit": u, "moves": "p95_ms",
         "workloads": ["yi6b_vocab.tail"]}
        for n, u in (("convert_s", "s"), ("plan_build_s", "s"),
                     ("panel_kernel_roofline.tail", "%"),
                     ("idle_pct.tail", "%"), ("queue_p95_ms.tail", "ms"),
                     ("batch_width.tail", "req/batch"))],
}


def benchmark_with_tail():
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    for key, extra in TAIL_ENTRIES.items():
        bench[key] = bench[key] + extra
    return bench


def cell(name):
    return harness.load_cell(name, benchmark_with_tail())


def rehearse(name, seed=2 ** 31 + 11, seconds=2.0, wrap=None):
    return harness.run_cell(cell(name), seed, seconds, False,
                            time.perf_counter(), jax.devices(),
                            rehearsal=True, wrap_product=wrap)


def test_benchmark_json_keeps_the_contract():
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [c["name"] for c in bench["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert UNIT.match(m["unit"]) and 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["per_layer"]:
        for w in m["workloads"]:
            cell_e2e = [x["name"] for x in harness.load_cell(w).end_to_end]
            assert m["moves"] in cell_e2e, (m["name"], w)
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(harness.ROOT, c["file"]))
        assert c["file"].startswith("bench/configs/")
    assert {w["name"] for w in bench["workloads"]} == {"hpcg104.cg"}
    assert {c["name"] for c in bench["configs"]} == {"hpcg104"}


@pytest.mark.parametrize("name", CELLS)
def test_every_file_a_cell_names_exists(name):
    c = cell(name)
    harness.load_plugin("loops", c.traffic["loop"])
    harness.load_plugin("gen", c.config["generator"])
    for m in c.per_layer:
        assert callable(harness.load_plugin("metrics", m["name"]).read)
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_is_correct_and_reports_no_device_metric(name):
    res = rehearse(name)
    assert res["correct"], res["checks"]
    assert res["metrics"] == {} and "rehearsal" in res
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_check(name):
    """The bfloat16 reference in the program's place must read incorrect."""
    res = rehearse(name, wrap=control.bf16_product)
    assert not res["correct"]
    assert res["checks"]["rel_gap"]["value"] > 10 * \
        res["checks"]["rel_gap"]["limit"]


def _cmd(tmp_root, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, os.path.join(tmp_root, "bench", "run.py"),
         "--workload", "hpcg104.cg", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300, cwd=tmp_root)


def test_run_without_a_tpu_fails_and_prints_no_result():
    res = _cmd(harness.ROOT)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert not [ln for ln in res.stdout.splitlines() if ln.startswith("{")]


def test_run_with_only_the_benchmark_files_fails(tmp_path):
    shutil.copytree(os.path.join(harness.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    res = _cmd(str(tmp_path))
    assert res.returncode != 0
    assert not [ln for ln in res.stdout.splitlines() if ln.startswith("{")]


def test_result_line_is_json_with_checks_last():
    res = rehearse("hpcg104.cg", seconds=1.0)
    line = json.loads(json.dumps(res))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
