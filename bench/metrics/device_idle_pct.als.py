"""Device idle share of the ALS window."""
from bench import readers


def read(run):
    return readers.device_idle_pct(run)
