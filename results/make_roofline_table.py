"""Render EXPERIMENTS.md roofline tables from dryrun JSONL sinks, plus the
masked-secure-agg kernel roofline (bytes moved vs in-kernel PRF VPU work)."""
import json
import sys

from repro.launch.analysis import TARGET_KIND, peaks

# --- masked-kernel roofline --------------------------------------------------
THREEFRY_OPS_PER_WORD = 38  # Threefry-2x32-13: ~76 int ops / 2 output words


def masked_kernel_roofline_row(B: int, D: int, degree: int = 0,
                               device_kind: str = TARGET_KIND) -> dict:
    """Roofline entry for one fused masked accumulation (B, D) session.

    The fused kernel reads x and uniforms (f32) and writes the int32 sum —
    the mask lane adds ZERO HBM bytes because every tile's mask words are
    regenerated in VMEM from (session key, pair, position) counters.  The
    pre-fusion path materialized a (B, D) int32 mask array in HBM (one
    write + one read).  ``t_mem_us`` is the kernel's HBM time at the
    published peak of ``device_kind``; the mask lane's VPU time has no
    published peak to divide by and is left to a chip trace.
    """
    hbm = peaks(device_kind)["hbm_bytes_per_s"]
    deg = (B - 1) if (degree <= 0 or degree >= B - 1) else degree
    graph = "complete" if deg == B - 1 else f"ring-{deg}"
    fused_bytes = 2 * B * D * 4 + B * 4 + D * 4  # x + uniforms + w + out
    mask_hbm_bytes = 2 * B * D * 4  # materialized masks: write + readback
    mask_words = B * deg * D  # per-row neighbour streams, regenerated
    return {
        "B": B, "D": D, "graph": graph, "device_kind": device_kind,
        "fused_hbm_bytes": fused_bytes,
        "mask_hbm_bytes_saved": mask_hbm_bytes,
        "mask_vpu_ops": mask_words * THREEFRY_OPS_PER_WORD,
        "t_mem_us": fused_bytes / hbm * 1e6,
    }


def write_masked_kernel_roofline(path: str, points,
                                 device_kind: str = TARGET_KIND) -> None:
    """points: iterable of (B, D, degree) -> markdown table at ``path``."""
    rows = [masked_kernel_roofline_row(B, D, deg, device_kind)
            for B, D, deg in points]
    hbm = peaks(device_kind)["hbm_bytes_per_s"]
    with open(path, "w") as f:
        f.write(
            "# Masked secure-agg kernel roofline\n\n"
            "In-kernel PRF mask generation (Threefry-2x32-13 counters, see\n"
            "`repro/kernels/prf.py`) vs the HBM traffic of the fused\n"
            "weight/quantize/accumulate kernel, counted from shapes.  The\n"
            "mask lane moves no bytes; what it costs is VPU work, counted\n"
            "here as int ops.  `t_mem` is the HBM time at the published\n"
            f"peak of a {device_kind} ({hbm / 1e9:.0f} GB/s).  No peak is\n"
            "published for the VPU's int32 rate, so whether the lane hides\n"
            "under the memory time is not computed here: it needs a chip\n"
            "trace (kernel time against `t_mem`), and is not measured yet.\n\n"
            "| B | D | graph | fused HBM bytes | mask HBM bytes saved | "
            "mask VPU ops | t_mem |\n"
            "|---|---|---|---|---|---|---|\n")
        for r in rows:
            f.write(
                f"| {r['B']} | {r['D']} | {r['graph']} | "
                f"{r['fused_hbm_bytes']:.2e} | "
                f"{r['mask_hbm_bytes_saved']:.2e} | "
                f"{r['mask_vpu_ops']:.2e} | {r['t_mem_us']:.1f}us |\n")


def fmt_t(s):
    if s is None:
        return "n/a"
    if s == 0:
        return "0"
    if s >= 1.0:
        return f"{s:.2f}s"
    if s >= 1e-3:
        return f"{s * 1e3:.2f}ms"
    return f"{s * 1e6:.0f}us"


def main(path):
    rows = [json.loads(l) for l in open(path)]
    print("| arch | shape | t_compute | t_memory | t_collective | dominant | "
          "peak/dev | useful FLOPs ratio | coll bytes/dev |")
    print("|---|---|---|---|---|---|---|---|---|")
    for r in rows:
        if "skipped" in r:
            print(f"| {r['arch']} | {r['shape']} | — | — | — | SKIP "
                  f"({r['skipped'][:40]}…) | — | — | — |")
            continue
        if "error" in r:
            print(f"| {r['arch']} | {r['shape']} | — | — | — | ERROR | — | — | — |")
            continue
        rf = r["roofline"]
        mem = r["memory"]
        ratio = rf.get("useful_flops_ratio", 0)
        print(f"| {r['arch']} | {r['shape']} | {fmt_t(rf['t_compute_s'])} | "
              f"{fmt_t(rf['t_memory_s'])} | {fmt_t(rf['t_collective_s'])} | "
              f"**{rf['dominant']}** | {mem['peak_bytes_est'] / 2**30:.1f}GiB | "
              f"{ratio:.2f} | "
              f"{rf['collectives']['total_wire_bytes']:.2e} |")


if __name__ == "__main__":
    main(sys.argv[1])
