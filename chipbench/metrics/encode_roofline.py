"""Shared pipeline encode (``aggregation.encode_plan_contribution``, where
``quantize_mask_prf`` runs): the encode's required HBM bytes (read the f32
delta, write the int32 row; ``work.encode_bytes``) at the chip's peak
bandwidth, over the device time of the encode program, per call."""
PROGRAMS = ("_masked_encode",)


def read(run):
    from chipbench import work
    t = run.trace
    if t is None:
        return None
    s, calls = t.program_seconds(PROGRAMS), t.program_calls(PROGRAMS)
    if s <= 0 or calls <= 0:
        return None
    least = calls * work.encode_bytes(run.d) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / s
