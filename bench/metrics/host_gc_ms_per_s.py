"""See ``bench.program_spans.gc_ms_per_s``: the paper-workflow cells."""
from bench.program_spans import gc_ms_per_s as read  # noqa: F401
