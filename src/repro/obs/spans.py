"""Host spans on the profiler's own clock.

``span(name, **args)`` opens a ``jax.profiler.TraceAnnotation``.  While a
profiler session records (``jax.profiler.trace`` or ``start_trace``) it
writes a host event into the session's ``.xplane.pb``, on the clock of
the device planes beside it, so one trace shows what the host did while
the device waited.  With no session it records nothing and costs about a
microsecond.  ``args`` become the event's stats; a value known only
inside the span goes in through ``set_metadata`` on the object the
``with`` binds.

The program's spans (docs/observability.md):

    api.<op>          one api round, ``DistProblem.sddmm/spmm/spmm_t/
                      fusedmm``; args ``family``, ``elision``
    api.put           the family's operand placement for the round
    api.session_key   ``Session``'s content key of one operand; ``hit``
                      when the identity memo answered without a digest
    api.digest        a missed key's blake2b of the operand; ``bytes``,
                      ``chunks`` (hashed on a thread pool from 8 MiB)
    api.upload        one host-to-device ``jax.device_put``; ``bytes``
    api.assemble      the host result's assembly
    api.wait          waiting for a device result about to be copied
    api.fetch         that result's device-to-host copy; ``bytes``
    als.cg_host       the host arithmetic of one CG step
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

__all__ = ["span"]


def span(name: str, **args) -> TraceAnnotation:
    """A host span named ``name`` with stats ``args``; use as ``with``."""
    return TraceAnnotation(name, **args)
