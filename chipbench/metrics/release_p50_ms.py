"""Median over all releases of the window of the time from the flush
trigger (the session's last arrival handed to the service) to released
params ready on the device (host clock, ``block_until_ready``)."""
import numpy as np


def read(run):
    return float(np.percentile(run.release_ms, 50))
