"""Host spans at the program's layer boundaries, on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``nq.<name>``.  With no profiler session it costs about a microsecond
and records nothing; inside ``jax.profiler.start_trace`` /
``stop_trace`` each span lands in the same xplane, on the same clock, as
the device operations, and its arguments come back as the event's
stats.  The profiler session is the switch, the buffer and the
exporter: there is no flag and nothing is kept here.  No span syncs
with the device, so a span around an asynchronous dispatch times the
dispatch; the device's side is in the trace beside it.

The spans, what each covers, and its arguments:

- ``nq.generate``: all of ``ServeEngine.generate``.  ``batch`` (the
  engine's count of earlier ``generate`` calls), ``experts_held`` (0
  for a model without experts), ``rows``, ``real_rows``
  (rows with uid >= 0), ``prompt_len``, ``steps``, ``rung`` (the rung
  served, set once the mode is ensured).
- ``nq.ensure_mode``: ``ServeEngine.ensure_mode``, the policy's decision
  and ``store.apply``.
- ``nq.prefill``: the prefill dispatch.  ``prompt_len``.
- ``nq.cache_rehome``: the prompt's cache moved into the ``max_len``
  buffer, and the nested KV cache's ingest.
- ``nq.token_sync``: one transfer of the step's tokens, one step
  behind: the host reads the tokens of step ``step - 1`` (the prefill's
  for step 0) once the decode of ``step`` is queued, and hands them to
  the live rows.  ``step``, ``rows``.
- ``nq.decode_step``: one decode dispatch and its argmax; it opens
  before the same step's ``nq.token_sync``.  ``step``.
- ``nq.switch``: ``NestQuantStore.apply``.  ``from_rung``, ``to_rung``
  (-1 for a per-leaf assignment).
- ``nq.page_in.read``: one array's bytes read from its segment file
  (``Artifact.read_array``).  ``nbytes``.
- ``nq.page_in.crc``: the CRC-32 of one array read
  (``Artifact.read_array``).  ``nbytes``.
- ``nq.page_in.put``: one fetched delta stream put on the device
  (``FilePager.fetch``).  ``nbytes``.
- ``nq.expert_load``: the one transfer of a batch's routing count (MoE
  only), after its last token is read.  ``held``, and ``rows_max`` and
  ``rows_mean``: the rows one decode step routed to a held expert of a
  layer, the most and the mean over layers and held experts.

The packed kernels show in the device trace as custom calls named after
their entry: ``packed_matmul``, ``nested_matmul`` and ``ladder_matmul``
(2-D weights at rung 0, 1 and deeper), and ``expert_packed_matmul``,
``expert_nested_matmul`` and ``expert_ladder_matmul`` (the grouped
matmul over an expert stack, ``kernels/expert_matmul``).
"""
from __future__ import annotations

import jax

PREFIX = "nq."


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``nq.<name>`` carrying ``args`` as trace stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
