"""kernel_roofline.serve: % of their roofline the attention kernels reach in
the traced window (bench/readers.py)."""
from bench.readers import kernel_roofline as read  # noqa: F401
