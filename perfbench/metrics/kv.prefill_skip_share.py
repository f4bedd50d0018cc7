"""kv.prefill_skip_share: prompt tokens served from prefix-tree pages over all
admitted prompt tokens in the window."""
from bench.readers import prefill_skip_share as read  # noqa: F401
