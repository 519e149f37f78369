"""See ``bench.readers.device_idle``: the paper-workflow cells."""
from bench.readers import device_idle as read  # noqa: F401
