"""Engine ingest: device time per contribution of the row-store program
(``AsyncServer._write_row``), from the profiler trace."""
PROGRAMS = ("_write_row",)


def read(run):
    t = run.trace
    if t is None or not run.contributions:
        return None
    s = t.program_seconds(PROGRAMS)
    return s / run.contributions * 1e3 if s > 0 else None
