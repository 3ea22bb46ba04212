"""singa_tpu.observe — unified tracing + metrics for train, serve, comms.

The telemetry layer of the ROADMAP north star: one event model
(``trace.py`` spans/instants, and ``phase()`` — the step-level sites of
the serve engine and the graph runner, always written as
``jax.profiler.TraceAnnotation`` so they sit on the device trace's
clock), one process-wide metrics surface
(``registry.py`` Counter/Gauge/Histogram, adopting the
``utils.metrics`` percentile machinery), and three exporters
(``export.py``: JSONL, Chrome trace-event JSON for Perfetto,
Prometheus text).  Instrumented out of the box: graph-mode compile vs
replay (``model._GraphRunner``), optimizer updates (``opt``),
collectives (``parallel.communicator``), checkpoints (``snapshot`` /
``Model.save_states``), and the serving engine's prefill / decode /
retire loop (``serve.engine``, whose ``EngineStats`` registers its
counters here).

Tracing is OFF by default and costs one flag check per site when off;
the registry is always on (counter bumps, vLLM-style).  See
docs/OBSERVABILITY.md.

Since the request-tracing round, ``requests.py`` adds a per-REQUEST
lifecycle ledger over the serve stack: one timeline per
``GenerationRequest`` (queue wait, cold/warm admission, per-step
emission, supervisor-restart and fleet-failover hops), a bounded JSONL
request log, per-request Chrome-trace tracks with hop flow arrows, and
the ``health_report()["serve"]["why_slow"]`` tail-latency attribution
(``requests.enable()`` — off by default, one flag read per hook when
off).

Since PR 3 there is also an ACTIVE layer over the passive one
(``monitor.py`` + ``health.py``): an always-on flight recorder with
crash bundles (``monitor.install_crash_handler``), MFU/goodput
accounting against a per-backend peak-FLOPs table, a hang/anomaly
watchdog fed by heartbeats from the graph runner and the serve decode
loop, declarative serve SLOs (``SLO``), and the one-call
:func:`health_report` summary.  See docs/OBSERVABILITY.md.

    from singa_tpu import observe
    observe.enable()
    observe.monitor.start()          # recorder + watchdog + MFU
    ...train / serve...
    observe.export.write_chrome_trace("/tmp/trace.json")
    print(observe.export.prometheus_text())
    print(observe.health_report()["train"]["mfu"])
"""

from . import export  # noqa: F401
from . import trace  # noqa: F401
from .registry import (Counter, Gauge, Histogram,  # noqa: F401
                       MetricsRegistry, registry)
from .trace import (clear, disable, drain, dropped,  # noqa: F401
                    enable, event, events, is_enabled, phase,
                    set_max_events, span, traced)
from . import stepprof  # noqa: F401  (step-anatomy profiler:
#                                      host/device attribution)
from .stepprof import StepProfiler  # noqa: F401
from . import monitor  # noqa: F401  (imports trace/registry only)
from . import requests  # noqa: F401  (per-request lifecycle ledger)
from .requests import RequestLedger  # noqa: F401
from . import timeseries  # noqa: F401  (windowed telemetry rings)
from .timeseries import WindowedFamily, WindowRing  # noqa: F401
from . import slo  # noqa: F401  (multi-window burn-rate alerting)
from .slo import BurnRule, SLOPolicy  # noqa: F401
from . import federate  # noqa: F401  (cross-host merge: clocks,
#                                      traces, metrics, why_slow)
from .federate import ClockSync, FleetTelemetry  # noqa: F401
from . import health  # noqa: F401
from .health import SLO, health_report  # noqa: F401
