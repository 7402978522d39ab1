"""Seconds of the whole window per completed FusedMM round."""
from bench import readers


def read(run):
    return readers.per_unit(run, run.window_s, "rounds")
