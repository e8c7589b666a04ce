"""itl_p95_ms: see ``bench.readers.itl_p95_ms``."""
from bench.readers import itl_p95_ms as read  # noqa: F401
