"""step.mfu.batch: counted model FLOPs over (seconds with a request in flight x
peak bf16 FLOP/s)."""
from bench.readers import step_mfu as read  # noqa: F401
