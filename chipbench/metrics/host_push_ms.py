"""Service facade: mean host self time per contribution of the program's own
ingest spans (``push`` / ``push_encoded`` on ``AsyncServer``, ``ingest`` /
``push_encoded`` on ``ShardedAsyncServer``), recorded unfenced, so what the
host spends dispatching and bookkeeping, not what the device spends."""
SPANS = ("push", "push_encoded", "ingest")


def read(run):
    from chipbench.trace import self_time_ns
    if not run.spans or not run.contributions:
        return None
    return self_time_ns(run.spans, SPANS) / run.contributions / 1e6
