"""Whole step: the aggregation's required bytes (``work.step_bytes``: every
folded contribution encoded, stored and read back once, the params read and
written once per release) over the traced window, as a share of the summed
peak bandwidth of the chips the cell holds.  It bounds every kernel's share
and still holds once a later change takes a kernel off the path."""


def read(run):
    from chipbench import work
    t = run.trace
    if t is None or t.window_s <= 0 or not run.releases:
        return None
    need = work.step_bytes(run.d, run.contributions, len(run.releases))
    peak = run.chips * run.peaks["hbm_bytes_per_s"]
    return 100.0 * need / (run.window_s * peak)
