"""kernels_roofline.batch: least time the counted work needs on the chip over
device busy time in the trace."""
from bench.readers import kernels_roofline as read  # noqa: F401
