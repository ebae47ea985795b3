"""One run of one cell: resolve it by name, set up, warm up, measure, check.

Everything that belongs to a configuration, a traffic mix or a metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

  <config file named in BENCHMARK.json>   engine, its arguments, FL settings
  chipbench/traffic/<traffic>.json        the mix's parameters
  chipbench/metrics/<metric>.py           ``read(run) -> float | None``
                                          (``<quantity>.py`` serves a split
                                          ``<quantity>.<part>``)

so a cell, a mix or a metric is added by adding files and entries.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
BENCHMARK = HERE.parent / "BENCHMARK.json"
SAMPLE = 2  # releases drawn from the seed for the check (+ the longest)


@dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, Callable]


def _load_reader(path: Path) -> Callable:
    mod_name = "chipbench_metric_" + "".join(
        c if c.isalnum() else "_" for c in path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise ValueError(f"cannot load metric reader {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _reader_path(root: Path, metric: str) -> Path:
    """``metrics/<metric>.py``; a metric split by the end-to-end metric it
    moves (``flush_ms.rounds``) shares its quantity's reader
    (``metrics/flush_ms.py``) unless it has one of its own."""
    metrics = root / "chipbench" / "metrics"
    own = metrics / f"{metric}.py"
    return own if own.exists() else metrics / f"{metric.split('.')[0]}.py"


def resolve(name: str, bench_path: Path = BENCHMARK) -> Cell:
    """The cell ``name`` with its configuration, mix and metric readers."""
    from chipbench import traffic
    root = bench_path.resolve().parent
    bench = json.loads(bench_path.read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in {bench_path} (known: "
                       f"{sorted(work)})")
    w = work[name]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    mix = traffic.load(w["traffic"], root / "chipbench" / "traffic")

    def applies(m: dict, e2e_names) -> bool:
        if "workloads" in m:
            return name in m["workloads"]
        return e2e_names is None or m["moves"] in e2e_names

    e2e = [m for m in bench["end_to_end"] if applies(m, None)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if applies(m, names)]
    readers = {m["name"]: _load_reader(_reader_path(root, m["name"]))
               for m in e2e + per_layer}
    return Cell(name, w, config, mix, e2e, per_layer, readers)


@dataclass
class Run:
    """What a metric reader reads."""

    cell: Cell
    setup_s: float
    window_s: float
    releases: list
    d: int  # parameters per delta
    chips: int
    peaks: dict
    trace: Any = None  # chipbench.trace.Summary of the traced window
    spans: list = field(default_factory=list)  # telemetry spans in window

    @property
    def contributions(self) -> int:
        return sum(r.contributions for r in self.releases)

    @property
    def release_ms(self) -> np.ndarray:
        return np.asarray([(r.ready - r.due) * 1e3 for r in self.releases])


class CompileCounter:
    """Counts lowerings and backend compiles while ``active``."""

    EVENTS = {"/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
              "/jax/core/compile/backend_compile_duration": "compiled",
              "/jax/compilation_cache/cache_retrieval_time_sec": "loaded"}

    def __init__(self):
        import jax.monitoring
        self.active = False
        self.counts = {v: 0 for v in self.EVENTS.values()}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if self.active and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def close(self):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on)


def _reservoir(seed: int):
    """Which releases of the window the check samples: SAMPLE drawn from
    the seed by reservoir sampling, plus the first with the most absent
    slots (the longest recovery)."""
    from chipbench import traffic
    rs = traffic.rng_for(seed, 7)
    state = {"seen": 0, "kept": {}, "longest": (-1, None)}

    def decide(absent_next: int) -> Optional[str]:
        i = state["seen"]
        state["seen"] += 1
        if absent_next > state["longest"][0]:
            return "longest"
        if i < SAMPLE:
            return f"r{i}"
        j = int(rs.integers(0, i + 1))
        return f"r{j}" if j < SAMPLE else None

    return state, decide


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def run_cell(cell: Cell, seed: int, seconds: float, trace_on: bool, *,
             t_process: float, log=print, require_tpu: bool = True) -> dict:
    """One run; returns the result object (the last line of stdout)."""
    import jax
    from chipbench import drive, reference, work
    from chipbench import trace as trace_mod

    chips = int(cell.workload["chips"])
    backend = jax.default_backend()
    if require_tpu and backend != "tpu":
        raise SystemExit(f"chipbench: JAX found no TPU (platform "
                         f"{backend!r}); the benchmark runs only on the chip")
    if len(jax.devices()) < chips:
        raise SystemExit(f"chipbench: cell {cell.name} needs {chips} chips, "
                         f"JAX sees {len(jax.devices())}")
    devs = jax.devices()[:chips]
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    peaks = work.peaks(dev.device_kind) if require_tpu else {}

    from repro.core import telemetry as tele
    tel = tele.Telemetry(record_spans=trace_on, fence=False)
    params = drive.make_params(cell.config, seed)
    pool = drive.make_pool(params, cell.mix, seed)
    d = sum(int(x.size) for x in jax.tree.leaves(params))
    eng = drive.build_engine(cell.config, params, tel)
    driver = drive.make_driver(eng, pool, cell.mix, seed)
    del params
    warm = drive.warm_up(driver)
    jax.block_until_ready(eng.params)
    log(f"setup: d={d} warm-up sessions={warm}")

    counter = CompileCounter()
    tel.spans.clear()
    state, decide = _reservoir(seed)
    tracer = trace_mod.Tracer(devs) if trace_on else None
    t_setup = time.perf_counter()
    setup_s = t_setup - t_process
    if tracer:
        tracer.start()
    counter.active = True
    t0 = time.perf_counter()
    releases = []
    while True:
        tag = decide(driver.next_absent())
        rel = driver.session(keep=tag is not None)
        releases.append(rel)
        if tag == "longest":
            state["longest"] = (rel.absent, rel)
        elif tag is not None:
            state["kept"][tag] = rel
        if rel.ready - t0 >= seconds:
            break
    window_s = releases[-1].ready - t0
    counter.active = False
    summary = None
    if tracer:
        summary = tracer.stop(
            window=(int(t0 * 1e9), int(releases[-1].ready * 1e9)), tel=tel)
    counter.close()
    log(f"window: {window_s:.3f} s, {len(releases)} releases, "
        f"{sum(r.contributions for r in releases)} contributions; "
        f"compiles in window: {sum(counter.counts.values())} "
        f"({', '.join(f'{k} {v}' for k, v in counter.counts.items())})")
    mem_peak = memory_peak(devs)

    spans = list(tel.spans)
    sample = list(state["kept"].values())
    if state["longest"][1] is not None:
        sample.append(state["longest"][1])
    entries = drive.pool_entries(pool, drive.stack_rows(cell.mix))
    cfg_fl = cell.config["fl"]
    del eng, driver
    gc.collect()
    readings = []
    for rel in sample:
        readings.append(reference.compare(
            rel.after, rel.before, entries, rel.weights, rel.contributions,
            rel.total_weight, clip_norm=cfg_fl["clip_norm"],
            noise_multiplier=cfg_fl["noise_multiplier"], rng=rel.rng,
            field_bits=cfg_fl["secure_agg_bits"],
            contributors=contributors(cell.config),
            value_range=cfg_fl.get("secure_agg_range", 4.0)))
        rel.before = rel.after = None
    limit = float(cell.config["check"]["err_over_bound"])
    worst = max(readings) if readings else math.inf
    failed = sum(1 for x in readings if not x <= limit)
    ok = bool(readings) and failed == 0

    run = Run(cell, setup_s, window_s, releases, d, chips, peaks, summary,
              spans)
    want = cell.per_layer if trace_on else cell.end_to_end
    metrics = {}
    for m in want:
        v = cell.readers[m["name"]](run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    ms = run.release_ms
    log(f"samples: {len(releases)} releases (release_ms p50 "
        f"{np.percentile(ms, 50):.4f}, p95 {np.percentile(ms, 95):.4f}), "
        f"{run.contributions} contributions, {len(readings)} checked")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    out = {"correct": ok, "attempted": len(readings), "failed": failed,
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    out["check"] = {"err_over_bound": [worst, limit]}
    return out


def contributors(config: dict) -> int:
    """Session slots of the configured engine (the field is sized for a
    full aggregate of that many rows)."""
    a = config["engine_args"]
    if "buffer_size" in a:
        return int(a["buffer_size"])
    return int(a["num_leaves"]) * int(a["leaf_buffer"])
