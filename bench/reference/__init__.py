"""The plain references the benchmark's ``correct`` is decided against."""
