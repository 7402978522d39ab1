"""Pallas kernel device time per CG matvec, busiest device."""
from bench import readers


def read(run):
    return readers.kernel_ms(run, "matvecs")
