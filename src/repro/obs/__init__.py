"""``repro.obs`` — serving observability: metrics, tracing, export.

Three pillars (see ISSUE 7 / README "Observability"):

  1. :mod:`repro.obs.metrics` — process-local counters / gauges /
     log-bucket histograms, JSON + Prometheus text export, no deps.
  2. :mod:`repro.obs.trace` — request-lifecycle spans in a bounded ring
     buffer with Chrome/Perfetto ``trace_event`` JSON export, and the
     program spans (``span``, listed in ``SPANS``) that land on the
     ``jax.profiler`` timeline beside the device's ops.
  3. :mod:`repro.obs.observer` — the single seam the engine emits
     through.  Off by default (``ServeConfig.obs`` falsy → ``NULL``, a
     shared no-op stub), host-timestamp-only, and lint-enforced to add
     zero host-transfer primitives to traced programs
     (``NoHostTransferInObsHooks``).
"""
from repro.obs.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                               MetricsRegistry)
from repro.obs.trace import (SPANS, TraceBuffer, Track,  # noqa: F401
                             engine_track, request_track, slot_track, span)
from repro.obs.observer import (NULL, NullObserver, Observer,  # noqa: F401
                                activated, get_active)
from repro.obs.export import (request_events, serving_obs_doc,  # noqa: F401
                              snapshot, validate_perfetto, write_json,
                              write_perfetto)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "SPANS", "TraceBuffer", "Track", "engine_track", "request_track",
    "slot_track", "span",
    "NULL", "NullObserver", "Observer", "activated", "get_active",
    "request_events", "serving_obs_doc", "snapshot", "validate_perfetto",
    "write_json", "write_perfetto",
]
