"""See ``bench.program_spans.cp_self_ms_per_pod``: the paper-workflow
cells."""
from bench.program_spans import cp_self_ms_per_pod as read  # noqa: F401
