"""Named spans of the restore and serving path, on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: while a
profiler trace runs it records one host event on the thread that opened
it, with ``args`` as the event's stats, on the same clock as the device's
operations; otherwise it costs about a microsecond.  Spans sit beside the
``perf_counter`` timers that feed ``RestoreStats`` and
``UploadStream.stats``; they are not a second timing system.

Every span of the serving path carries ``function`` (the function it
serves) and ``req`` (the node's sequence number of the invocation it
belongs to; :data:`NO_REQ` where no invocation owns the work).

    serve.invoke      node worker: one attempt at an invocation, up to its
                      result (arg ``role``: warm, owner, joined or payload)
    serve.generate    generate, up to the first token on the host
    serve.dispatch    one call of the embed, layer or head program
    serve.resolve     one wait for a layer's, the embedding's or the
                      final norm's parameters
    spice.read        prefetch reader: one storage op of a restore stream
    spice.ring_wait   prefetch reader: handing a tensor to the upload ring
                      (blocks while ``depth`` jobs are outstanding)
    spice.upload.issue ring's issuer: one job's whole issue, fused or full
                      (its put, and a fused job's patch and un-paging
                      dispatches)
    spice.upload.put  ring's issuer: one host-to-device put, inside its
                      job's spice.upload.issue
    spice.upload.land ring's lander: one wait for an issued job to land
"""
from __future__ import annotations

NO_REQ = -1
_annotation = None  # jax.profiler.TraceAnnotation, bound on first use


def span(name: str, **args):
    global _annotation
    if _annotation is None:
        # bound here: the host-only restore path does not import jax
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation(name, **args)
