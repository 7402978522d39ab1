"""Host ms per FusedMM round placing operands (``api.put``, inclusive)."""
from bench import spans


def read(run):
    return spans.per_unit_ms(run, "api.put", "inclusive", "rounds")
