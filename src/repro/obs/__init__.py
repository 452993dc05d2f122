"""Unified runtime observability (DESIGN.md §16).

Three pieces, one subsystem:

* :mod:`repro.obs.metrics` — counters / gauges / histograms with labeled
  series behind a :class:`MetricsRegistry`, snapshotted deterministically
  (inject a ``VirtualClock``) and exported as Prometheus text or JSON.
  The serve layer (scheduler, service, admission, setup cache) and the
  backend capability-fallback path all report through it.
* :mod:`repro.obs.timeline` — Chrome-trace (catapult JSON) timelines:
  measured host-side phase spans (``jax.profiler.TraceAnnotation`` +
  wall clock), the static HLO overlap schedule from
  ``repro.utils.trace``, virtual-time replay timelines, and telemetry
  tracks decoded from the on-device ring.
* the **on-device telemetry ring** itself lives with the solver
  (``repro.core.pipelined_cg`` / ``repro.core.types.TelemetrySlab`` /
  ``repro.kernels.fused_iter.tel_layout``) — this package only decodes
  and renders it.

The solve's device-clock spans are its **phase scopes**
(``repro.core.types.PHASE_TAGS``): ``jax.named_scope`` scopes that reach
the compiled program as instruction ``op_name`` metadata.  A profiler
trace names the instructions it ran, so joining the two gives device
time per phase on the trace's own clock (the chip benchmark's
``phase_reduce.py``); this package keeps no span clock of its own.
"""

from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               default_registry)
from repro.obs.timeline import (Timeline, hlo_schedule_track, replay_timeline,
                                solve_timeline, telemetry_track)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "default_registry",
    "Timeline",
    "hlo_schedule_track",
    "replay_timeline",
    "solve_timeline",
    "telemetry_track",
]
