"""forge.dispatches_per_tick.agent: decode-front segments dispatched plus host
ops replayed, per decode call."""
from bench.readers import dispatches_per_tick as read  # noqa: F401
