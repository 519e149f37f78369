"""See ``bench.readers.device_idle``: the ML pipeline cells."""
from bench.readers import device_idle as read  # noqa: F401
