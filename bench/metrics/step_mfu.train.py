"""step_mfu.train: required operations of the window's training steps
(forward and backward, real points) over window time x chips x bf16 peak
(bench/readers.py)."""
from bench.readers import mfu as read  # noqa: F401
