"""sched.loop_idle_share.agent: device idle inside the program's scheduler
ticks (``serve.tick`` less ``serve.wait_arrival``) over the traced window."""
from bench.readers import loop_idle_share as read  # noqa: F401
