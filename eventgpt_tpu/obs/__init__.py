"""Unified telemetry: metrics registry, request/step tracing, profiling.

Three pillars, one package (OBSERVABILITY.md is the operator doc):

  * ``obs.metrics``   — process-global, thread-safe counters / gauges /
    log2-bucket histograms, exposed as Prometheus text (``GET /metrics``
    on the serving front end) and merged into ``/stats``; the trainer
    writes the same registry to a per-step ``telemetry.jsonl``.
  * ``obs.trace``     — the one span probe: a ring-buffered
    ``perf_counter`` interval at every layer boundary (with its parent
    and its request ids), exported as Chrome trace events
    (``--trace_out``, ``GET /trace``) loadable in Perfetto /
    chrome://tracing, and the same interval as a
    ``jax.profiler.TraceAnnotation`` while the profiler is armed.
  * ``obs.profiling`` — ``jax.profiler`` hooks: arming, step annotations
    around train steps plus an on-demand capture window
    (``POST /profile``).

Design rules shared by all three (the ``faults.py`` discipline):
stdlib-only at import (``metrics``/``trace`` never import jax, so they
are safe before backend init and in spawned workers), disarmed cost is
one module-global check per call site, and instrumentation is
chain-neutral — it reads clocks and counts events, never touches a jax
array, so decoded chains are byte-identical with telemetry on or off
(tested: ``tests/test_obs.py::test_chain_neutrality``).
"""
