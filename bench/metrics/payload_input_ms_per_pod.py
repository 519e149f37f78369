"""Host input preparation and upload per pod: the program's
``payload.input`` span (numpy draw and ``jnp.asarray``)."""
from bench.program_spans import ms_per_call


def read(rec):
    return ms_per_call("payload.input")
