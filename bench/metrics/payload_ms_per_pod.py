"""Mean wall time of one payload call, host clock around the callable,
which ends in ``block_until_ready``."""


def read(rec):
    w = rec.window
    if not w.get("pods"):
        return None
    return w["payload_s"] / w["pods"] * 1e3
