"""decode_roofline.b1: see ``bench.readers.decode_roofline``."""
from bench.readers import decode_roofline as read  # noqa: F401
