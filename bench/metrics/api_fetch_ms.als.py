"""Host ms per CG matvec copying results back (``api.fetch``)."""
from bench import spans


def read(run):
    return spans.per_unit_ms(run, "api.fetch", "inclusive", "matvecs")
