"""Spans of the served path, on the profiler's clock.

Every span the program emits goes through :func:`span`: a
``jax.profiler.TraceAnnotation`` named ``fleet.<name>``, which lands on the
host plane of a JAX profiler trace beside the device's operations, on the
same clock. It records only while a JAX profiler trace is active
(``jax.profiler.start_trace`` … ``stop_trace``); otherwise it costs one
object's creation and writes nothing. There is no other switch.

A span's args are counters read back from the trace as the event's stats:
counts already at hand (lengths, ``nbytes``), never a pass over data. An
arg known only once the work is done goes on with the annotation's
``set_metadata``.

The spans, from the gateway down:

* ``fleet.dispatch`` — one flushed admission window (``dispatch``: its
  sequence number, ``requests``, ``wait_ms_sum``, ``wait_ms_max``,
  ``window_ms``); the spans below it belong to that window by nesting;
* ``fleet.scatter`` — the fan-out over partitions;
* ``fleet.leg`` — one partition's leg (``partition``, ``cold``);
* ``fleet.hydrate`` — index state read, grown, rebuilt or derived from an
  earlier generation in a handler (``h2d_bytes``: state placed on the
  device);
* ``fleet.encode`` — tokenizing and encoding a BM25 batch (``queries``);
* ``fleet.bm25`` / ``fleet.dense`` — one tier's device call, until its
  outputs are on the host (``queries``, ``padded``, ``h2d_bytes``: the
  host arrays handed to the device, :func:`host_nbytes`, once per call);
  ``fleet.bm25`` also counts ``gathered``, the postings its (T, M) rung
  gathers (padded · T · M · block), and ``full``, 1 when it ran the full
  (``max_terms``, ``max_blocks``) rung, else 0;
* ``fleet.backfill`` — a lazy partition's off-path backfill;
* ``fleet.merge``, ``fleet.kv``, ``fleet.materialize`` — the
  coordinator's gather: merge and fusion, the KV fetch (``keys``), the
  response bodies;
* ``fleet.commit`` — one refresh, from the call until every pool holds the
  new generation (``added``, ``deleted``, ``merged`` partitions,
  ``published_bytes`` put to the store, ``h2d_bytes`` it placed on the
  device outside the pools' hydrations), over its phases
  ``fleet.commit.apply`` (stats, vocabulary, tiers), ``.write`` (the
  writers' pack and upload), ``.publish`` (shared state and manifests),
  ``.kv`` (``keys``: passages put), ``.prewarm`` (the generation's idf,
  then the pools' pings, whose ``fleet.hydrate`` spans nest inside;
  ``pings``) and ``.gc``.
"""

from __future__ import annotations

import jax
import numpy as np

PREFIX = "fleet."


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """The span ``fleet.<name>`` with ``args`` as its counters."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


def host_nbytes(*arrays) -> int:
    """Bytes of those ``arrays`` that sit on the host (numpy), which a
    device call copies over; an array already on the device counts 0."""
    return sum(a.nbytes for a in arrays if isinstance(a, np.ndarray))
