"""decode_step_ms.b1: see ``bench.readers.decode_step_ms``."""
from bench.readers import decode_step_ms as read  # noqa: F401
