"""Dispatch and device wait per pod: the program's ``payload.compute``
span (the jitted body and its ``block_until_ready``)."""
from bench.program_spans import ms_per_call


def read(rec):
    return ms_per_call("payload.compute")
