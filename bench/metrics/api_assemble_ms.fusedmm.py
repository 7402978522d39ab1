"""Host ms per FusedMM round assembling the output (``api.assemble``,
self: less the wait and the copy back)."""
from bench import spans


def read(run):
    return spans.per_unit_ms(run, "api.assemble", "self_s", "rounds")
