"""repro.obs — span tracing and serving telemetry.

The paper's method IS measurement: Fig. 3 attributes HE Mul time to
CRT/NTT/modmul/iCRT, and every optimization in the paper follows from
that attribution. The served step carries that attribution itself:
`dist.he_pipeline` wraps each stage call in a `jax.named_scope`
(``he.crt``, ``he.ntt``, ``he.intt``, ``he.modmul``, ``he.icrt``), so a
`jax.profiler` trace of the fused step names each device op's stage.
This package gives the host side of the runtime the same lens:

  - :class:`Tracer` (`trace.py`) — nested spans with injectable clocks,
    exported as Chrome trace-event JSON (Perfetto / chrome://tracing)
    and mirrored, while open, as ``hserve.*`` profiler host annotations
    on the device trace's clock. Request lifecycle (submit → enqueue →
    bucket_wait → flush → batch_assemble → dispatch → device_wall →
    complete) and the spans of one batch through the server (poll,
    h2d, launch, wait, retire, prefetch, warm compiles, table-slice
    fetches).
  - :class:`MetricsRegistry` (`registry.py`) — counters, gauges, and
    bounded histograms plus pull-based sources (ServeMetrics,
    TableCache, CircuitScheduler, HESession all publish), snapshot as
    JSON on demand and embedded in `runtime.monitor.Heartbeat`
    payloads — the health channel the multi-host tier will consume.
`python -m repro.obs report trace.json` prints the queue-wait vs
device-wall latency decomposition (`report.py`).

See docs/OBSERVABILITY.md for the span taxonomy and naming contract.
"""

from repro.obs.registry import MetricsRegistry, merge_snapshots
from repro.obs.stats import Reservoir
from repro.obs.trace import Span, Tracer

__all__ = ["MetricsRegistry", "merge_snapshots", "Reservoir", "Span",
           "Tracer"]
