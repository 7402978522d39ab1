"""Pallas kernel device time per FusedMM round, busiest device."""
from bench import readers


def read(run):
    return readers.kernel_ms(run, "rounds")
