"""Engine flush: device time per release of the flush programs (the masked
buffer step with or without recovery, the tier's two-level step; each
jitted as ``step``), from the profiler trace."""
PROGRAMS = ("step",)


def read(run):
    t = run.trace
    if t is None or not run.releases:
        return None
    s = t.program_seconds(PROGRAMS)
    return s / len(run.releases) * 1e3 if s > 0 else None
