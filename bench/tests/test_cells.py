"""BENCHMARK.json and the files it names: every cell resolves, every
metric has its reader, the device table refuses what it does not know,
and a run without a TPU exits non-zero with no result."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench.harness import cell, device

from .conftest import ROOT

BENCH = cell.benchmark(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_to_its_files(workload):
    c = cell.resolve(BENCH, workload)
    program, ref, work = cell.family(c["config"])
    assert cell.mode(c["traffic"]).run
    shape = program.shape(c["config"])
    need = work.work(shape)
    assert need["flops"] > 0 and need["bytes"] > 0
    assert {m["name"] for m in c["end_to_end"]} >= {"setup_s"}
    assert len(c["end_to_end"]) >= 2 and c["per_layer"]
    for m in c["end_to_end"] + c["per_layer"]:
        assert callable(cell.metric_reader(m["name"]).read)
    assert c["config"]["check"]["max_abs_error"] > 0


def test_names_and_references_are_consistent():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    configs = {c["name"] for c in BENCH["configs"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert {w["config"] for w in BENCH["workloads"]} == configs
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(e2e[m["moves"]].get("workloads",
                                                              cells))
    for c in BENCH["configs"]:
        data = cell.load_json(os.path.join(ROOT, c["file"]))
        assert set(c["reduced"]) == set(data["reduced"])


LINE = re.compile(r"^[^\t\r\n]{1,200}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_the_file_keeps_its_form():
    assert set(BENCH) == KEYS["top"]
    assert len(json.dumps(BENCH, indent=1)) <= 64 * 1024
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    assert 1 <= len(BENCH["command"]) <= 32
    assert all(LINE.match(w) for w in BENCH["command"])
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = len(BENCH["workloads"])
    assert 1 <= cells <= 24 and 1 <= len(BENCH["configs"]) <= 24
    # A full check at 24 cells has to fit the driver's 43200 s.
    full = 2 + 14 * 24
    assert full * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    for c in BENCH["configs"]:
        assert set(c) == KEYS["configs"]
        assert LINE.match(c["why"]) and LINE.match(c["source"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    assert len({c["file"] for c in BENCH["configs"]}) == len(BENCH["configs"])
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == KEYS["workloads"]
        assert LINE.match(w["why"]) and w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == cells
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, cells // 2)
    for kind in ("end_to_end", "per_layer"):
        assert 1 <= len(BENCH[kind]) <= (16 if kind == "end_to_end" else 128)
        for m in BENCH[kind]:
            assert set(m) - {"workloads"} == KEYS[kind]
            assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert LINE.match(m["layer"])


def test_unknown_device_kind_is_an_error():
    assert device.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks_for("TPU v9 imaginary")


def test_a_run_without_a_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_the_result_line_of_a_cpu_run(cpu_harness, workload):
    out, run = cpu_harness(workload)
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {m["name"] for m in run.cell["end_to_end"]}
    assert out["device"]["platform"] == "cpu"
    json.dumps(out, allow_nan=False)
