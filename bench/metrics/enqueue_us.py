"""enqueue_us: see ``bench.readers.enqueue_us``."""
from bench.readers import enqueue_us as read  # noqa: F401
