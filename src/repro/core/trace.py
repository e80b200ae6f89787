"""The engine's names in the profiler's trace.

Two kinds of name, both written into the one trace that
``jax.profiler.trace`` captures, so host and device share its clock:

* **Device scopes** (:data:`SCOPES`): ``jax.named_scope`` around each
  engine stage where it is traced.  They land in the ``op_name``
  metadata of the compiled ops (``jit(_pipeline_body)/rungen/while``),
  add no runtime work, and never nest inside each other:

  - ``rungen``: run generation (input batching, engine init, the scan,
    the drain), the streamed absorb, and a snapshot's or close's drain;
  - ``premerge``: the pre-wide merge levels (§4.3);
  - ``wide_merge``: the wide merge;
  - ``exchange``: the key-range cuts, segment packing and ``all_to_all``;
  - ``owner_merge``: each range owner's merge of what it received.

* **Host spans** (:func:`span`): ``repro.*`` events on the host
  thread that made the call, each with id arguments so that one
  request's spans group together.  The outer ones are the calls users
  make: ``repro.aggregate`` (``query=``), ``repro.ingest``
  (``chunk=``), ``repro.snapshot`` (``snapshot=``), ``repro.close``.
  Inside them: ``repro.pack`` (key packing), ``repro.pad`` (host
  padding to the batch geometry), ``repro.place`` (explicit
  ``device_put``), ``repro.dispatch`` (a call of a compiled engine
  program) and ``repro.readback`` (``what=stats|occupancy|observation``,
  a blocking device-to-host read).

With no profiler running a span costs about a microsecond; nothing is
recorded outside the profiler.
"""
from __future__ import annotations

import jax

SCOPES = ("rungen", "premerge", "wide_merge", "exchange", "owner_merge")


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` (one of the ``repro.*`` names above) in the
    profiler's trace, with ``ids`` as its arguments; use as a context
    manager."""
    return jax.profiler.TraceAnnotation(name, **ids)
