"""Engine ingest, device side: the device idle time that the trace's
attribution (``Summary.idle_gaps``, each gap to the innermost span the host
was in) puts in ``store`` spans, per contribution.  None without a trace or
where the program has no ``store`` span; 0.0 when no gap falls in one."""
SPAN = "store"


def read(run):
    t = run.trace
    if t is None or not run.contributions:
        return None
    if not any(sp.name == SPAN for sp in run.spans):
        return None
    return dict(t.idle_gaps).get(SPAN, 0.0) / run.contributions * 1e3
