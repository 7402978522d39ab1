"""Host ms per CG matvec keying Session operands by content
(``api.session_key``)."""
from bench import spans


def read(run):
    return spans.per_unit_ms(run, "api.session_key", "inclusive", "matvecs")
