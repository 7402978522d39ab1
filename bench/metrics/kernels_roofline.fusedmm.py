"""Roofline share of the FusedMM rounds' kernels."""
from bench import readers


def read(run):
    return readers.kernels_roofline(run)
