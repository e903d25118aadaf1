"""Fabric observability: span tracing, latency metrics, static cost probes.

Three parts (DESIGN.md §10), all layered OVER the fabric — nothing in this
package participates in a coherence decision, and with tracing disabled
(the default) the instrumentation costs <1% on the batched serving path
(the paper's own overhead bar, pinned by tests/test_obs.py):

  * ``trace``    — a low-overhead host-side span tracer emitting
    Chrome-trace/Perfetto-compatible JSON; spans wrap every fabric batch
    lifecycle phase (pack → exchange → scan → miss pass → decode →
    donate) and every scheduler wave phase, plus the jit-dispatch vs
    device-execute split via ``block_until_ready`` fencing; while enabled
    each span is also a ``jax.profiler.TraceAnnotation``.
  * ``metrics``  — log-bucketed latency histograms with exact
    p50/p95/p99 summaries.
  * ``xprof``    — static cost probes: a jaxpr walker counting collectives
    (the generalization of ``pipeline.collective_counts``) plus compiled
    cost analysis (FLOPs, bytes accessed) per fabric function.
"""
from repro.obs.metrics import LatencyHistogram
from repro.obs.trace import Tracer, disable, enable, get_tracer, set_tracer
from repro.obs.xprof import cost_probe, jaxpr_collectives

__all__ = [
    "LatencyHistogram", "Tracer",
    "enable", "disable", "get_tracer", "set_tracer",
    "cost_probe", "jaxpr_collectives",
]
