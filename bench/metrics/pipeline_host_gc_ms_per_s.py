"""See ``bench.program_spans.gc_ms_per_s``: the ML pipeline cells."""
from bench.program_spans import gc_ms_per_s as read  # noqa: F401
