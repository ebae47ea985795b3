"""Shared set-up of the benchmark's own tests (run on the CPU):

    python -m pytest chipbench/tests
"""
import copy
import json
import sys
import time
from pathlib import Path

import pytest

CHECKOUT = Path(__file__).resolve().parents[2]
for p in (str(CHECKOUT / "src"), str(CHECKOUT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# a small synthetic pytree in place of whisper-tiny: the CPU runs the same
# engines, traffic and check at a size a test run can hold
TINY_MODEL = {"shapes": {"w1": [256, 128], "b1": [128], "w2": [128, 64]}}

# cells whose configuration and traffic files are in chipbench/ but which
# BENCHMARK.json does not hold yet (not proven on the chip): the tests run
# them through a copy of the benchmark file that adds these entries
CANDIDATES = {
    "configs": [
        {"name": "tier4-tee-whisper-tiny", "source": "see file",
         "file": "chipbench/configs/tier4-tee-whisper-tiny.json",
         "reduced": [], "why": "a candidate"}],
    "workloads": [
        {"name": "tier4.backlog", "config": "tier4-tee-whisper-tiny",
         "traffic": "backlog4", "chips": 4, "why": "a candidate"}],
}


def write_bench(root: Path) -> Path:
    """``root/BENCHMARK.json``: the benchmark file plus the candidate
    cells, beside a link to this checkout's ``chipbench/``."""
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    for key, entries in CANDIDATES.items():
        bench[key] = bench[key] + entries
    (root / "chipbench").symlink_to(CHECKOUT / "chipbench")
    path = root / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return path


@pytest.fixture(scope="session")
def bench_path(tmp_path_factory):
    return write_bench(tmp_path_factory.mktemp("bench"))


@pytest.fixture
def tiny_cell(bench_path):
    """``tiny_cell(name)``: the resolved cell (a candidate too) with the
    tiny model."""
    from chipbench import harness

    def make(name):
        cell = harness.resolve(name, bench_path)
        cell.config = copy.deepcopy(cell.config)
        cell.config["model"] = dict(TINY_MODEL)
        return cell

    return make


@pytest.fixture
def run_tiny():
    """``run_tiny(cell, seed, seconds=1.0, trace=False)`` on the CPU."""
    from chipbench import harness

    def run(cell, seed, seconds=1.0, trace=False):
        return harness.run_cell(cell, seed, seconds, trace,
                                t_process=time.perf_counter(),
                                log=lambda s: None, require_tpu=False)

    return run
