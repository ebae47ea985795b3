"""Shared pipeline encode, host side: mean host self time of the engine's
``encode`` spans (``AsyncServer._encode_for_slot``: the session key, the
per-slot rng and the dispatch of ``_masked_encode``), recorded unfenced, so
what the host spends issuing the encode, not what the device spends.  None
where the program has no ``encode`` span."""
SPANS = ("encode",)


def read(run):
    from chipbench.trace import self_time_ns
    n = sum(1 for sp in run.spans if sp.name in SPANS)
    if not n:
        return None
    return self_time_ns(run.spans, SPANS) / n / 1e6
