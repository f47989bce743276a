"""engine_host_ms.serve: per request, the benchmark's request span minus the
device-busy time inside it, mean over the window (bench/readers.py)."""
from bench.readers import host_ms_per_request as read  # noqa: F401
