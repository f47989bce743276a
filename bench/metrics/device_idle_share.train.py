"""device_idle_share.train: % of the traced window in which the device ran no
operation (bench/readers.py)."""
from bench.readers import idle_share as read  # noqa: F401
