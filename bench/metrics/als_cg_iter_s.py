"""Seconds of the whole window per completed CG matvec."""
from bench import readers


def read(run):
    return readers.per_unit(run, run.window_s, "matvecs")
