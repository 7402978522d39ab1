"""Host ms per CG matvec of CG arithmetic (``als.cg_host``)."""
from bench import spans


def read(run):
    return spans.per_unit_ms(run, "als.cg_host", "inclusive", "matvecs")
