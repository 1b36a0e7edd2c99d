# Multi-stage DAG jobs with per-stage replication policies (DESIGN.md §12).
#
# The paper's native workload is MapReduce: map → shuffle → reduce, each
# stage ending in a straggler-sensitive barrier, replication chosen *per
# stage*.  This subsystem models that scenario class on top of repro.fleet:
#   * `graph`   — StageSpec / JobDAG (validated topological stage order,
#     linear pipelines and general fan-in barriers);
#   * `rollout` — the fused stage-composed vectorized engine: a whole
#     (λ × per-stage-policy-vector) grid as ONE device program chaining
#     `masked_single_fork` per stage through the barrier max, stage queues
#     via the shared `fleet.vector.batched_queue` cell engine (Lindley /
#     Kiefer–Wolfowitz scan / Pallas kw_queue kernel);
#   * `search`  — joint per-stage policy search (coordinate ascent +
#     exhaustive small grids) with critical-path attribution;
#   * `engine`  — discrete-event ground truth: one FleetScheduler per stage
#     pool on a shared heap, jobs re-entering the queue per stage through
#     barrier-release events.
from .graph import JobDAG, StageSpec  # noqa: F401
from .rollout import (  # noqa: F401
    DagRolloutResult,
    dag_frontier,
    dag_rollout,
    lower_dag_frontier,
    vector_label,
)
from .search import (  # noqa: F401
    best_stable,
    coordinate_search,
    exhaustive_search,
    uniform_vectors,
)
from .engine import (  # noqa: F401
    DagFleetConfig,
    DagFleetReport,
    DagFleetScheduler,
    DagFleetSim,
    DagJobRecord,
    poisson_arrivals,
    run_dag_fleet,
)

__all__ = [
    "DagFleetConfig",
    "DagFleetReport",
    "DagFleetScheduler",
    "DagFleetSim",
    "DagJobRecord",
    "DagRolloutResult",
    "JobDAG",
    "StageSpec",
    "best_stable",
    "coordinate_search",
    "dag_frontier",
    "dag_rollout",
    "exhaustive_search",
    "lower_dag_frontier",
    "poisson_arrivals",
    "run_dag_fleet",
    "uniform_vectors",
    "vector_label",
]
