"""Device idle share of the FusedMM rounds' window."""
from bench import readers


def read(run):
    return readers.device_idle_pct(run)
