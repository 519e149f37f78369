"""See ``bench.readers.cp_ms_per_pod``: the ML pipeline cells."""
from bench.readers import cp_ms_per_pod as read  # noqa: F401
