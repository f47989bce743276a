"""forward_mfu.serve: required forward operations of the points served in
the window over window time x chips x bf16 peak (bench/readers.py)."""
from bench.readers import mfu as read  # noqa: F401
