"""sched.occupancy: the scheduler's occupied row-steps over capacity row-steps
in the window (run() counters)."""
from bench.readers import occupancy as read  # noqa: F401
