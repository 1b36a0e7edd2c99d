"""repro.obs — unified observability for the fleet stack.

One package, four capabilities (DESIGN.md §13):

  * `sketch`    — mergeable streaming quantile sketch (DDSketch-style);
  * `registry`  — counters / gauges / sketch-backed histograms with labels;
  * `trace`     — span recorder + NullRecorder zero-cost-when-disabled
    protocol, and `host_span` on the JAX profiler's clock; `export`
    renders Chrome trace-event JSON for Perfetto;
  * `decisions` — structured decision log for the adaptive controller;
  * `device`    — in-program γ-bucket histograms for the fused engines;
  * `profile`   — wall-time / HLO-byte / memory profiling of jitted fns,
    plus re-trace detection for the padded-replan contract;
  * `evtail`    — peaks-over-threshold GPD tails fitted on sketch buckets
    (`extreme_quantile` beyond what the sample resolves, DESIGN.md §16);
  * `slo`       — SLO objects + multi-window error-budget burn rates;
  * `blame`     — per-machine straggler attribution (counterfactual tail);
  * `dashboard` — single-file HTML / terminal report over all of it.

Quick start::

    from repro import obs
    rec = obs.enable()                      # process-wide recorder
    report = FleetSim(FleetConfig(capacity=8, obs=True)).run(jobs)
    obs.write_chrome_trace("trace.json", report.trace)
"""

from .blame import BlameScore, StragglerBlame  # noqa: F401
from .dashboard import (  # noqa: F401
    render_dashboard,
    render_text,
    write_dashboard,
)
from .decisions import (  # noqa: F401
    DecisionEvent,
    DecisionLog,
    KIND_BLAME,
    KIND_DRIFT,
    KIND_EXPLORE,
    KIND_REPLAN,
    KIND_VETO,
)
from .device import (  # noqa: F401
    DEFAULT_HIST,
    HistSpec,
    device_histogram,
    sketch_from_device,
)
from .export import (  # noqa: F401
    load_chrome_trace,
    to_chrome_trace,
    write_chrome_trace,
)
from .evtail import (  # noqa: F401
    EVTail,
    GPDFit,
    domain_of_fit,
    evt_keys,
    fit_gpd,
    gpd_params_of,
)
from .profile import RetraceWatch, jit_cache_size, kernel_profile  # noqa: F401
from .registry import Counter, Gauge, Histogram, MetricsRegistry  # noqa: F401
from .sketch import QuantileSketch, merge_all  # noqa: F401
from .slo import SLO, SLOTracker, WindowedSketch, trackers_for  # noqa: F401
from .trace import (  # noqa: F401
    NULL_RECORDER,
    NullRecorder,
    PID_CONTROLLER,
    PID_DAG_BASE,
    PID_FLEET,
    PID_PROFILER,
    PID_SERVING,
    Recorder,
    disable,
    enable,
    get_recorder,
    host_span,
)

__all__ = [
    "QuantileSketch", "merge_all",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "Recorder", "NullRecorder", "NULL_RECORDER",
    "enable", "disable", "get_recorder", "host_span",
    "PID_FLEET", "PID_CONTROLLER", "PID_SERVING", "PID_PROFILER",
    "PID_DAG_BASE",
    "DecisionEvent", "DecisionLog",
    "KIND_REPLAN", "KIND_DRIFT", "KIND_EXPLORE", "KIND_VETO", "KIND_BLAME",
    "HistSpec", "DEFAULT_HIST", "device_histogram", "sketch_from_device",
    "to_chrome_trace", "write_chrome_trace", "load_chrome_trace",
    "kernel_profile", "jit_cache_size", "RetraceWatch",
    "EVTail", "GPDFit", "fit_gpd", "evt_keys", "domain_of_fit",
    "gpd_params_of",
    "SLO", "SLOTracker", "WindowedSketch", "trackers_for",
    "BlameScore", "StragglerBlame",
    "render_dashboard", "render_text", "write_dashboard",
]
