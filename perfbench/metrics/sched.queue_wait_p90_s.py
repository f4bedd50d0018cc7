"""sched.queue_wait_p90_s: 90th percentile over the window's requests of the
wait from the due arrival to the start of the admission that granted the
slot (``queue_wait_s`` of the program's run() result; a request deferred
for pages waits until the admission that succeeds)."""
from typing import Dict, Optional

import numpy as np


def read(ctx: Dict) -> Optional[float]:
    waits = [r["queue_wait_s"] for r in ctx["res"]["results"].values()
             if r.get("queue_wait_s") is not None]
    return float(np.percentile(np.asarray(waits, np.float64), 90)) if waits else None
