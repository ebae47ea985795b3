"""Roofline analysis from compiled dry-run artifacts (no hardware needed).

Three terms per (arch, shape, mesh), in seconds:
  compute    = HLO_FLOPs_per_device / peak_FLOP/s
  memory     = HLO_bytes_per_device / HBM_bw
  collective = sum over collective ops of wire_bytes_per_device / link_bw

GSPMD emits a per-partition module, so cost_analysis numbers are already
per-device.  Collective bytes are parsed from the optimized HLO text (they
are NOT in cost_analysis); wire factors: all-reduce 2x (ring = reduce-scatter
+ all-gather), all-gather / reduce-scatter / all-to-all / collective-permute
1x of the result-shard size.

Hardware constants come from :data:`PEAKS`, keyed by ``device_kind``; the
production mesh (``launch/mesh.py``) is a TPU v5e, :data:`TARGET_KIND`.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.  Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s).  The ICI figure is per LINK and an assumption
# (one ~50 GB/s link per transfer, conservative), not a published peak.
# A kind missing here is an error, never a default.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "ops_int8": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_link_bytes_per_s": 50e9,
    },
}
TARGET_KIND = "TPU v5 lite"  # the chip of make_production_mesh


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table of one chip kind; raises for a kind not in it."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add its figures to PEAKS with their "
            f"source") from None


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_WIRE_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\w+\[[\d,]*\][^ ]*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


def _shape_bytes(s: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(s):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str, loop_multiplier: float = 1.0) -> List[Dict]:
    """Sum result-shard bytes of every collective in the optimized HLO.

    loop_multiplier: collectives in NON-ENTRY computations (while/scan bodies)
    execute once per loop trip — scale them by the trip count; entry-level
    collectives execute once.
    """
    out = []
    in_entry = True
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            in_entry = True
        elif line and not line[0].isspace() and line.rstrip().endswith("{"):
            in_entry = False  # a non-entry computation definition begins
        m = _OP_RE.search(line)
        if m:
            shape_str, kind = m.group(1), m.group(2)
            b = _shape_bytes(shape_str)
            mult = 1.0 if in_entry else loop_multiplier
            out.append({"kind": kind, "bytes": b * mult,
                        "wire_bytes": b * _WIRE_FACTOR[kind] * mult,
                        "in_entry": in_entry})
    return out


def collective_summary(hlo_text: str, loop_multiplier: float = 1.0) -> Dict:
    ops = parse_collectives(hlo_text, loop_multiplier)
    by_kind: Dict[str, Dict] = {}
    for op in ops:
        e = by_kind.setdefault(op["kind"], {"count": 0, "bytes": 0, "wire_bytes": 0})
        e["count"] += 1
        e["bytes"] += op["bytes"]
        e["wire_bytes"] += op["wire_bytes"]
    return {
        "ops": by_kind,
        "total_bytes": sum(o["bytes"] for o in ops),
        "total_wire_bytes": sum(o["wire_bytes"] for o in ops),
        "count": len(ops),
    }


def roofline(compiled, hlo_text: str, *, model_flops: float = 0.0,
             chips: int = 1, multiplier: float = 1.0,
             device_kind: str = TARGET_KIND) -> Dict:
    """Three-term roofline from a compiled executable, against the peaks
    of ``device_kind`` (default: the production mesh's chip).

    model_flops: analytic 6*N*D (or 6*N_active*D) *global* FLOPs — compared
    against per-device HLO flops x chips for the usefulness ratio.
    multiplier: scale for cost-probe artifacts that lower one loop trip
    (e.g. one client chunk of n_chunks).
    """
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # older API returned [dict]
        cost = cost[0]
    flops = float(cost.get("flops", 0.0)) * multiplier
    bytes_accessed = float(cost.get("bytes accessed", 0.0)) * multiplier
    coll = collective_summary(hlo_text)
    if multiplier != 1.0:
        coll = {
            **coll,
            "total_bytes": coll["total_bytes"] * multiplier,
            "total_wire_bytes": coll["total_wire_bytes"] * multiplier,
        }

    p = peaks(device_kind)
    t_compute = flops / p["flops_bf16"]
    t_memory = bytes_accessed / p["hbm_bytes_per_s"]
    t_collective = coll["total_wire_bytes"] / p["ici_link_bytes_per_s"]
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_collective}
    dominant = max(terms, key=terms.get)

    out = {
        "flops_per_device": flops,
        "bytes_per_device": bytes_accessed,
        "collectives": coll,
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "dominant": dominant,
        "bound_time_s": max(terms.values()),
    }
    if model_flops > 0:
        total_hlo = flops * chips
        out["model_flops"] = model_flops
        out["useful_flops_ratio"] = model_flops / total_hlo if total_hlo else 0.0
    return out


def memory_summary(compiled) -> Dict:
    ma = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes")
    out = {}
    for k in keys:
        if hasattr(ma, k):
            out[k] = int(getattr(ma, k))
    args = out.get("argument_size_in_bytes", 0)
    out["peak_bytes_est"] = (args + out.get("temp_size_in_bytes", 0)
                             + out.get("output_size_in_bytes", 0)
                             - out.get("alias_size_in_bytes", 0))
    return out


def fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024:
            return f"{n:.2f} {unit}"
        n /= 1024
    return f"{n:.2f} PiB"


def fmt_time(s: float) -> str:
    if s >= 1.0:
        return f"{s:.3f} s"
    if s >= 1e-3:
        return f"{s * 1e3:.3f} ms"
    return f"{s * 1e6:.1f} us"
