"""The harness is driven by data: cells, mixes and metrics resolve by name,
a new cell is only new files and entries, generators are pure functions of
the seed, and the entry point refuses to run off the chip."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness, traffic

CHECKOUT = Path(__file__).resolve().parents[2]
BENCH = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_workload_resolves(workload):
    cell = harness.resolve(workload)
    assert cell.config["name"] == cell.workload["config"]
    assert cell.mix["kind"] in traffic.KINDS
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cell.readers[m["name"]])
    assert cell.config["chips"] == cell.workload["chips"]


def test_benchmark_file_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in BENCH["configs"]] + \
        [w["name"] for w in BENCH["workloads"]] + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/")
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        layers.setdefault(m["layer"], m["layer"])
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_a_cell_added_as_files_and_entries_is_picked_up(tmp_path):
    shutil.copytree(CHECKOUT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    root = tmp_path / "chipbench"
    cfg = json.loads((root / "configs" /
                      "papaya-tee-whisper-tiny.json").read_text())
    cfg["name"] = "papaya-k32-whisper-tiny"
    cfg["engine_args"]["buffer_size"] = 32
    (root / "configs" / "papaya-k32-whisper-tiny.json").write_text(
        json.dumps(cfg))
    (root / "traffic" / "backlog_fresh.json").write_text(json.dumps(
        {"kind": "backlog", "group": 1, "staleness":
         {"dist": "constant", "value": 0},
         "pool": {"size": 8, "median": 1.0, "sigma": 0.5}}))
    (root / "metrics" / "sessions_per_s.py").write_text(
        "def read(run):\n    return len(run.releases) / run.window_s\n")
    bench["configs"].append({
        "name": "papaya-k32-whisper-tiny", "source": "a test",
        "file": "chipbench/configs/papaya-k32-whisper-tiny.json",
        "reduced": [], "why": "a test"})
    bench["workloads"].append({
        "name": "papaya32.fresh", "config": "papaya-k32-whisper-tiny",
        "traffic": "backlog_fresh", "chips": 1, "why": "a test"})
    bench["per_layer"].append({
        "name": "sessions_per_s", "unit": "1/s", "better": "higher",
        "source": "host_clock", "layer": "engine flush",
        "moves": "contrib_per_s", "workloads": ["papaya32.fresh"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.resolve("papaya32.fresh", tmp_path / "BENCHMARK.json")
    assert cell.config["engine_args"]["buffer_size"] == 32
    assert cell.mix["staleness"]["dist"] == "constant"
    assert "sessions_per_s" in cell.readers
    assert [m["name"] for m in cell.per_layer] == ["sessions_per_s"]
    assert [m["name"] for m in cell.end_to_end] == ["contrib_per_s",
                                                    "setup_s"]
    # and the cells that were there resolve as before
    assert harness.resolve("papaya.backlog",
                           tmp_path / "BENCHMARK.json").mix["group"] == 1


@pytest.mark.parametrize("mix", sorted(
    p.stem for p in (CHECKOUT / "chipbench" / "traffic").glob("*.json")))
def test_traffic_is_a_pure_function_of_the_seed(mix):
    m = traffic.load(mix)
    seed = 2 ** 31 + 977
    if m["kind"] == "backlog":
        a, b = traffic.staleness(m, seed, 4096), traffic.staleness(m, seed,
                                                                   4096)
        assert np.array_equal(a, b)
        c = traffic.staleness(m, seed + 1, 4096)
        assert not np.array_equal(a, c)
        assert abs(a.mean() - m["staleness"]["mean"]) < 0.5
    else:
        a = traffic.absent_slots(m, seed, 32, 40)
        assert a == traffic.absent_slots(m, seed, 32, 40)
        b = traffic.absent_slots(m, seed + 1, 32, 40)
        # the same work for every seed: the multiset of counts is fixed
        assert sorted(map(len, a)) == sorted(map(len, b))
        if m["dropout"] > 0:
            assert a != b
            assert abs(np.mean([len(x) for x in a]) / 32 - m["dropout"]) \
                < 0.02
        else:
            assert all(x == [] for x in a)


def test_seed_words_take_large_seeds():
    assert traffic.seed_words(2 ** 33 + 5) != traffic.seed_words(5)
    assert all(0 <= w < 2 ** 32 for w in traffic.seed_words(2 ** 40))


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "chipbench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    p = _run(["--workload", "papaya.backlog", "--seed", "7", "--seconds",
              "1", "--trace", "0"], CHECKOUT)
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr
    assert '"correct"' not in p.stdout


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(CHECKOUT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(["--workload", "papaya.backlog", "--seed", "7", "--seconds",
              "1", "--trace", "0"], tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_candidate_cell_resolves(bench_path):
    """The cell whose files are here but not yet in BENCHMARK.json resolves
    once its entries are added."""
    cell = harness.resolve("tier4.backlog", bench_path)
    assert cell.config["chips"] == cell.workload["chips"] == 4
    assert {m["name"] for m in cell.end_to_end} == {"contrib_per_s",
                                                    "setup_s"}


def test_split_metric_shares_its_reader():
    cell = harness.resolve("secagg.dropout10")
    assert "flush_ms.rounds" in cell.readers
    assert "flush_ms.backlog" in harness.resolve("papaya.backlog").readers
