"""Host spans of the serve path, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation`` (a TraceMe event): under a
profiler session it lands on the ``/host:CPU`` plane of the same trace as
the device's operations, so every device gap can be put down to what the
host was doing.  With no session active a span costs a flag check.

``dispatch`` opens the frontend's span of one engine call and makes its
dispatch id current on the calling thread; every ``span`` opened inside it
on that thread carries the same id as metadata (``name#id=7#`` in the
TraceMe encoding, a stat ``id`` in the trace).

Spans (see ``serve/frontend.py`` and ``core/index.py``):

* ``frontend.wait``      the worker idle, with nothing queued;
* ``frontend.dispatch``  one engine call and its resolution (the parent;
  metadata ``id``, ``rows``, ``bucket``);
* ``frontend.batch``     concatenation and padding to the bucket;
* ``index.prepare``      query preprocessing, the threshold, host-to-device;
* ``index.launch``       the enqueue of the search program;
* ``index.fetch``        the results' copies to the host and their masking;
* ``frontend.resolve``   telemetry and the requests' futures.
"""
from __future__ import annotations

import contextlib
import contextvars

from jax.profiler import TraceAnnotation

_DISPATCH_ID: contextvars.ContextVar = contextvars.ContextVar(
    "dispatch_id", default=None)


def span(name: str, **meta) -> TraceAnnotation:
    """A host span, tagged with the current dispatch id if there is one."""
    did = _DISPATCH_ID.get()
    if did is not None:
        meta["id"] = did
    return TraceAnnotation(name, **meta)


@contextlib.contextmanager
def dispatch(did: int, **meta):
    """The ``frontend.dispatch`` span of dispatch ``did``."""
    token = _DISPATCH_ID.set(did)
    try:
        with TraceAnnotation("frontend.dispatch", id=did, **meta):
            yield
    finally:
        _DISPATCH_ID.reset(token)
