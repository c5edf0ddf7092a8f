"""The harness is driven by data: every cell finds its files by name, a new
cell needs only new files and entries, and a run without a TPU stops
before any work."""
import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run as bench_run  # noqa: E402
from bench.traffic import driver  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return bench_run.load_benchmark()


def test_every_cell_resolves_its_files(bench):
    for w in bench["workloads"]:
        cell, conf, cfg, mix = bench_run.resolve(bench, w["name"])
        assert (ROOT / conf["file"]).is_file()
        assert cfg["catalog"]["rows"] > 0 and cfg["engine"]["n_subsets"] > 0
        assert driver(mix["kind"]).needed(mix, bench["run_seconds"]) > 0
        for trace in (False, True):
            for m in bench_run.cell_metrics(bench, cell, trace):
                if trace:
                    assert callable(bench_run.metric_reader(m["name"]))


def test_the_file_keeps_to_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/")
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in bench[key]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        rep = [m["name"] for m in bench_run.cell_metrics(bench, w, False)]
        assert "setup_s" in rep and len(rep) >= 2
        assert bench_run.cell_metrics(bench, w, True)
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", []):
            assert cell in e2e[m["moves"]].get("workloads", [cell])
        if m["unit"] == "%" and "roofline" in m["name"]:
            assert m["name"].endswith("_roofline")


def test_an_added_cell_needs_only_new_files_and_entries(bench, tmp_path):
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    cfg = json.loads((ROOT / "bench/configs/bigearthnet-s2.json").read_text())
    cfg["catalog"]["rows"] = 1000
    (tmp_path / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "bench/traffic/closed-1.json").read_text())
    mix["clients"] = 2
    (tmp_path / "bench/traffic/two-callers.json").write_text(
        json.dumps(mix))
    b = json.loads(json.dumps(bench))
    b["configs"].append({"name": "tiny", "source": "https://example.org",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "a test"})
    b["workloads"].append({"name": "tiny.two", "config": "tiny",
                           "traffic": "two-callers", "chips": 1,
                           "why": "a test"})
    for m in b["end_to_end"]:
        if m["name"] == "query_p90_ms":
            m["workloads"].append("tiny.two")
    cell, _, got_cfg, got_mix = bench_run.resolve(b, "tiny.two", tmp_path)
    assert got_cfg["catalog"]["rows"] == 1000 and got_mix["clients"] == 2
    e2e = [m["name"] for m in bench_run.cell_metrics(b, cell, False)]
    assert set(e2e) == {"query_p90_ms", "setup_s"}
    per_layer = [m["name"] for m in bench_run.cell_metrics(b, cell, True)]
    assert "index_build_s" in per_layer
    for name in per_layer:
        assert callable(bench_run.metric_reader(name))


def test_no_tpu_exits_before_any_work():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    t0 = time.monotonic()
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         "rapidearth-1m.closed-1", "--seed", str(2 ** 31 + 7), "--seconds",
         "45", "--trace", "0"], cwd=str(ROOT), env=env,
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr and "built in" not in p.stderr
    assert time.monotonic() - t0 < 60


def test_a_tree_of_the_benchmark_alone_exits_non_zero(bench, tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        bench["command"] + ["--workload", "bigearthnet-s2.closed-1", "--seed",
                            "5", "--seconds", "3", "--trace", "0"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
