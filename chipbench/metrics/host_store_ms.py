"""Engine ingest, host side: mean host self time of the engine's ``store``
spans (``AsyncServer._store_row``: the dispatch of ``_write_row`` and the
slot bookkeeping, closed before a flush opens), recorded unfenced.  A
dispatch that waits for device memory waits here.  None where the program
has no ``store`` span."""
SPANS = ("store",)


def read(run):
    from chipbench.trace import self_time_ns
    n = sum(1 for sp in run.spans if sp.name in SPANS)
    if not n:
        return None
    return self_time_ns(run.spans, SPANS) / n / 1e6
