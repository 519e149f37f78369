"""Read-back and shared-volume hand-off per pod: the program's
``payload.output`` span (upstream ``volume.get``, the 4-value read-back
and ``volume.put``)."""
from bench.program_spans import ms_per_call


def read(rec):
    return ms_per_call("payload.output")
