"""bucket.prefill_pad_share: 1 - prompt tokens prefilled over prefill cells
executed (rows x columns) in the window."""
from bench.readers import prefill_pad_share as read  # noqa: F401
