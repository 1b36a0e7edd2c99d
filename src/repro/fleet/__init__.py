# Multi-job, finite-capacity fleet simulation (DESIGN.md §9).
#
# The paper analyzes one job on an unbounded pool; this subsystem puts the
# single-/multi-fork policies in a production regime: jobs arrive over time,
# compete for a finite worker pool, queue behind each other, and a
# replication decision for one job delays everything behind it.  Two paths:
#   * `FleetSim` — exact event-heap discrete-event engine (events.py,
#     scheduler.py), any admission discipline / preemption / relaunch delay;
#   * `repro.fleet.vector` — vmapped many-trial JAX rollouts for the
#     gang-aligned G/G/c regime (Kiefer–Wolfowitz recursion, heterogeneous
#     machine classes as per-slot speeds), for policy sweeps.
from .events import Event, EventHeap, OwnedHeap  # noqa: F401
from .workload import (  # noqa: F401
    Job,
    MachineClass,
    bursty_workload,
    diurnal_workload,
    piecewise_poisson_workload,
    poisson_workload,
    regime_shift_workload,
    trace_workload,
)
from .adaptive import (  # noqa: F401
    FleetPolicyController,
    PolicyDecision,
    as_policy_provider,
    ks_statistic,
)
from .scenarios import (  # noqa: F401
    CHAOS,
    ChaosScenario,
    REGIME_SHIFT,
    RegimeShiftScenario,
)
from .scheduler import FleetScheduler, JobRecord  # noqa: F401
# the chaos-engine declarative surface (repro.faults), re-exported because
# a FaultSpec is configured in the same breath as the FleetConfig using it
from repro.faults import (  # noqa: F401
    ChaosSchedule,
    CrashProcess,
    FaultSpec,
    Outage,
    effective_fail_prob,
    schedule_for_kill_fraction,
)
from .metrics import (  # noqa: F401
    DagStats,
    FleetStats,
    class_sojourn_sketches,
    compute_dag_stats,
    compute_stats,
    dag_critical_path_shares,
    straggler_blame,
    tail_quantiles,
)
from .fleet import FleetConfig, FleetReport, FleetSim, run_fleet  # noqa: F401
from . import vector  # noqa: F401
# the PR-4 fused-engine public surface, re-exported so examples and user
# code stop reaching into repro.fleet.vector by module path
from .vector import (  # noqa: F401
    fleet_rollout,
    frontier,
    lower_frontier,
    policy_search,
    sweep,
    trace_kill_rollout,
)

__all__ = [
    "CHAOS",
    "ChaosSchedule",
    "ChaosScenario",
    "CrashProcess",
    "DagStats",
    "Event",
    "EventHeap",
    "FaultSpec",
    "FleetConfig",
    "FleetPolicyController",
    "FleetReport",
    "FleetScheduler",
    "FleetSim",
    "FleetStats",
    "Job",
    "JobRecord",
    "MachineClass",
    "Outage",
    "OwnedHeap",
    "PolicyDecision",
    "REGIME_SHIFT",
    "RegimeShiftScenario",
    "as_policy_provider",
    "bursty_workload",
    "class_sojourn_sketches",
    "effective_fail_prob",
    "schedule_for_kill_fraction",
    "compute_dag_stats",
    "compute_stats",
    "dag_critical_path_shares",
    "straggler_blame",
    "diurnal_workload",
    "fleet_rollout",
    "frontier",
    "ks_statistic",
    "lower_frontier",
    "piecewise_poisson_workload",
    "poisson_workload",
    "policy_search",
    "regime_shift_workload",
    "run_fleet",
    "sweep",
    "tail_quantiles",
    "trace_kill_rollout",
    "trace_workload",
    "vector",
]
