"""Roofline share of the ALS half-rounds' kernels."""
from bench import readers


def read(run):
    return readers.kernels_roofline(run)
