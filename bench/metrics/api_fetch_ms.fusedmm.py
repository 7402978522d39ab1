"""Host ms per FusedMM round copying results back (``api.fetch``)."""
from bench import spans


def read(run):
    return spans.per_unit_ms(run, "api.fetch", "inclusive", "rounds")
