# The paper's primary contribution: malleable job scheduling.
#
# - strategies: EASY-BACKFILL (rigid) + MIN / PREF / AVG / KEEPPREF (paper §2.1)
# - simulator:  event-quantized-tick DES, bit-equivalent to per-tick ElastiSim
# - sim_jax:    fully-jittable lax.scan variant of the same scheduling math
# - speedup:    efficiency-threshold rigid->malleable transform (paper §2.2)
# - traces:     statistical twins of Haswell/KNL/Eagle/Theta + cleaning
# - metrics:    turnaround/makespan/wait/utilization with warm-up & drain-down
from .cluster import CLUSTERS, Cluster, EAGLE, HASWELL, KNL, THETA
from .jobs import (CLASS_NORMAL, CLASS_ON_DEMAND, CLASS_RIGID, DONE,
                   PENDING, QUEUED, RUNNING, Workload)
from .metrics import (Window, aggregate_seeds, backfill_starts,
                      improvement, iqr, run_metrics, scheduling_counters)
from .passes import (balanced_expand, balanced_shrink, greedy_expand,
                     greedy_shrink)
from .scenario import (JobClasses, ScenarioConfig, apply_scenario,
                       assign_job_classes)
from .simulator import SimResult, Simulator, simulate
from .speedup import (TabulatedSpeedup, TransformConfig, amdahl_efficiency,
                      amdahl_speedup, nodes_at_efficiency,
                      pfrac_for_reference_efficiency, progress_rate,
                      transform_rigid_to_malleable)
from .strategies import (AVG, EASY, KEEPPREF, MIN, PREF, STRATEGIES,
                         StrategySpec, get_strategy)
from . import traces

__all__ = [
    "CLUSTERS", "Cluster", "EAGLE", "HASWELL", "KNL", "THETA",
    "CLASS_NORMAL", "CLASS_ON_DEMAND", "CLASS_RIGID",
    "DONE", "PENDING", "QUEUED", "RUNNING", "Workload",
    "Window", "aggregate_seeds", "backfill_starts", "improvement",
    "iqr", "run_metrics", "scheduling_counters",
    "balanced_expand", "balanced_shrink", "greedy_expand", "greedy_shrink",
    "JobClasses", "ScenarioConfig", "apply_scenario", "assign_job_classes",
    "SimResult", "Simulator", "simulate",
    "TabulatedSpeedup", "TransformConfig", "amdahl_efficiency",
    "amdahl_speedup", "nodes_at_efficiency",
    "pfrac_for_reference_efficiency", "progress_rate",
    "transform_rigid_to_malleable",
    "AVG", "EASY", "KEEPPREF", "MIN", "PREF", "STRATEGIES", "StrategySpec",
    "get_strategy", "traces",
]
