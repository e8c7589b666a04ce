"""output_tok_s: see ``bench.readers.output_tok_s``."""
from bench.readers import output_tok_s as read  # noqa: F401
