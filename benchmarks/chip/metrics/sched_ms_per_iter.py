"""Scheduler time per iteration: LiveCluster.sched_time_s over
n_iterations, both changed over the window (policy and planner,
without engine execution)."""
from chipbench.readers import sched_ms_per_iter as read  # noqa: F401
