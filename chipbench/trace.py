"""The reduction from a profiler trace (xplane) and telemetry spans to numbers.

``Tracer`` records the window with ``jax.profiler`` and marks the host clock
in the trace with an annotation (``SYNC``), so the program's telemetry spans
(host ``perf_counter`` times) and the device's events land on one clock.
``summarize`` reduces the trace to a :class:`Summary`:

* busy: the union of the intervals in which an operation ran on a device,
  inside the window, averaged over the devices;
* device time and executions per program (jitted function, by the name XLA
  gives its module: ``jit_<name>``), and per collective operation;
* ``breakdown``: the device operations that took most time, and the idle
  gaps of the device grouped by the telemetry span the host was in.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SYNC = "chipbench.sync"
ANCHOR = "chipbench_anchor"  # a tiny program whose run pins the device clock
MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)
# an HLO collective by its opcode (``= <shape> all-reduce(``), not by an
# operand's name
COLLECTIVE = re.compile(
    r"[ )}](all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(-start|-done)?\(")
TOP = 10  # entries per breakdown list


def program_name(event_name: str) -> str:
    """``jit__write_row(42)`` -> ``_write_row``; ``jit_step`` -> ``step``."""
    name = re.sub(r"\(\d+\)$", "", event_name.strip())
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its HLO text
    (``%copy.15 = s32[...] copy(...)``): keep the instruction's name."""
    head = event_name.split(" = ", 1)[0]
    return head.lstrip("%")


def merge(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def self_time_ns(spans, names: Sequence[str]) -> int:
    """Summed self time of the named spans: each span's duration minus the
    part its direct children cover."""
    child: Dict[int, int] = {}
    for sp in spans:
        if sp.parent is not None:
            child[sp.parent] = child.get(sp.parent, 0) + sp.dur_ns
    return sum(sp.dur_ns - child.get(sp.sid, 0) for sp in spans
               if sp.name in names)


@dataclass
class Device:
    name: str
    modules: List[Tuple[str, int, int]] = field(default_factory=list)
    ops: List[Tuple[str, int, int]] = field(default_factory=list)


@dataclass
class Summary:
    """The traced window, reduced.  Times in seconds; per-device numbers
    averaged over the devices."""

    window_s: float
    busy_s: float
    programs: Dict[str, Tuple[float, float]]  # name -> (seconds, calls)
    collective_s: float
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    devices: int

    def program_seconds(self, names: Sequence[str]) -> float:
        return sum(s for n, (s, _) in self.programs.items() if n in names)

    def program_calls(self, names: Sequence[str]) -> float:
        return sum(c for n, (_, c) in self.programs.items() if n in names)

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.top_ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps]}


def devices_of(pd) -> Tuple[List[Device], Optional[int]]:
    """Device planes (modules and ops) and the trace time of ``SYNC``."""
    devs, sync = [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            dev = Device(plane.name)
            for line in plane.lines:
                evs = [(e.name, int(e.start_ns), int(e.end_ns))
                       for e in line.events]
                if line.name in MODULE_LINES:
                    dev.modules.extend(evs)
                elif line.name in OP_LINES:
                    dev.ops.extend(evs)
            if dev.modules or dev.ops:
                devs.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == SYNC and sync is None:
                        sync = int(e.start_ns)
    return devs, sync


def _label_at(t: int, spans_abs: List[Tuple[int, int, str, int]],
              starts: List[int]) -> str:
    """The innermost service span covering trace time ``t``.

    ``spans_abs`` is sorted by start.  Spans of one host thread nest, so
    the latest-starting span that covers ``t`` is the innermost; the walk
    back stops at a top-level span that ended before ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        s, e, name, d = spans_abs[i]
        if s <= t < e:
            return name
        if d == 0:
            break
        i -= 1
    return "outside service"


def _attribute(idle, spans_abs, starts, lo: int, hi: int):
    """Split idle intervals by the innermost service span the host was in:
    yields (label, seconds) pieces."""
    bounds = sorted({lo, hi} | {t for s, e, _, _ in spans_abs
                                for t in (s, e) if lo < t < hi})
    segs = [(a, b, _label_at((a + b) // 2, spans_abs, starts))
            for a, b in zip(bounds, bounds[1:])]
    k = 0
    for s, e in idle:
        while k < len(segs) and segs[k][1] <= s:
            k += 1
        j = k
        while j < len(segs) and segs[j][0] < e:
            a, b, label = segs[j]
            yield label, (min(b, e) - max(a, s)) / 1e9
            j += 1


def summarize(pd, *, window: Tuple[int, int], marks: dict,
              spans=(), span_epoch_ns: int = 0) -> Optional[Summary]:
    """Reduce a trace to a Summary.

    ``window``: (start, end) of the measured window on the host clock
    (``perf_counter_ns``).  ``marks``: ``sync_ns``, the host clock inside
    the ``SYNC`` annotation (it puts host events on the trace's clock), and
    ``anchor``, the host clock before dispatching ``ANCHOR`` and after it
    was ready (the device's events are shifted so the anchor's run sits in
    the middle of that interval: the device clock in the trace can be a
    millisecond off the host's).  ``spans``: telemetry SpanRecords, whose
    ``t0_ns`` count from ``span_epoch_ns`` on the host clock.  None when
    the trace holds no accelerator plane (a CPU run).
    """
    devs, sync = devices_of(pd)
    if not devs:
        return None
    off = (sync - marks["sync_ns"]) if sync is not None else 0
    lo, hi = window[0] + off, window[1] + off
    anchors = [(s, e) for name, s, e in devs[0].modules
               if program_name(name) == ANCHOR]
    if anchors and marks.get("anchor"):
        a0, a1 = (t + off for t in marks["anchor"])
        s0, e0 = anchors[0]
        shift = (a0 + a1) // 2 - (s0 + e0) // 2
        for dev in devs:
            dev.modules = [(n, s + shift, e + shift) for n, s, e in
                           dev.modules]
            dev.ops = [(n, s + shift, e + shift) for n, s, e in dev.ops]
    depth: Dict[int, int] = {}
    for sp in spans:
        depth[sp.sid] = 0 if sp.parent is None else depth.get(sp.parent,
                                                               0) + 1
    spans_abs = [(span_epoch_ns + sp.t0_ns + off,
                  span_epoch_ns + sp.t0_ns + sp.dur_ns + off, sp.name,
                  depth[sp.sid]) for sp in spans]
    spans_abs.sort()
    starts = [s for s, _, _, _ in spans_abs]
    n = len(devs)
    busy, coll = 0.0, 0.0
    programs: Dict[str, List[float]] = {}
    ops: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    for i, dev in enumerate(devs):
        evs = dev.ops or dev.modules
        merged = merge(clip(((s, e) for _, s, e in evs), lo, hi))
        busy += sum(e - s for s, e in merged) / 1e9
        for name, s, e in dev.modules:
            if e > lo and s < hi:
                p = programs.setdefault(program_name(name), [0.0, 0.0])
                p[0] += (min(e, hi) - max(s, lo)) / 1e9
                p[1] += 1
        mods = sorted((s, e, program_name(name))
                      for name, s, e in dev.modules)
        k = 0
        for name, s, e in sorted(dev.ops, key=lambda ev: ev[1]):
            if not (e > lo and s < hi):
                continue
            while k + 1 < len(mods) and mods[k + 1][0] <= s:
                k += 1
            prog = mods[k][2] if mods and mods[k][0] <= s < mods[k][1] \
                else "?"
            dt = (min(e, hi) - max(s, lo)) / 1e9
            key = f"{prog}/{op_name(name)}"
            ops[key] = ops.get(key, 0.0) + dt
            if COLLECTIVE.search(name):
                coll += dt
        if i == 0:  # idle gaps of the first device, by the host's span
            edges = [lo] + [t for iv in merged for t in iv] + [hi]
            idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
            for label, dt in _attribute(idle, spans_abs, starts, lo, hi):
                gaps[label] = gaps.get(label, 0.0) + dt
    top = sorted(((k, v / n) for k, v in ops.items()), key=lambda kv: -kv[1])
    return Summary(
        window_s=(hi - lo) / 1e9, busy_s=busy / n,
        programs={k: (v[0] / n, v[1] / n) for k, v in programs.items()},
        collective_s=coll / n, top_ops=top[:TOP],
        idle_gaps=sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP],
        devices=n)


def _anchor(x):
    return x + 1


_anchor.__name__ = ANCHOR


class Tracer:
    """Profiles the window into a temporary directory (under ``TMPDIR``),
    reduces it, and deletes it (``keep`` copies the xplane file first)."""

    def __init__(self, devices):
        import jax
        import jax.numpy as jnp
        self._fn = jax.jit(_anchor)
        self._x = jax.device_put(jnp.zeros((8, 128), jnp.float32),
                                 devices[0])
        jax.block_until_ready(self._fn(self._x))  # compiled before the trace
        self.marks: dict = {}

    def start(self) -> None:
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        jax.profiler.start_trace(self.dir)
        with jax.profiler.TraceAnnotation(SYNC):
            self.marks["sync_ns"] = time.perf_counter_ns()
        t0 = time.perf_counter_ns()
        jax.block_until_ready(self._fn(self._x))
        self.marks["anchor"] = (t0, time.perf_counter_ns())

    def stop(self, *, window: Tuple[int, int], tel,
             keep: Optional[str] = None) -> Optional[Summary]:
        import jax
        from jax.profiler import ProfileData
        jax.profiler.stop_trace()
        try:
            path = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                             recursive=True)[0]
            if keep:
                shutil.copy(path, keep)
            pd = ProfileData.from_file(path)
            return summarize(pd, window=window, marks=self.marks,
                             spans=tel.spans, span_epoch_ns=tel.epoch_ns)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
