"""The device's idle share of the traced window, in %: 1 minus the union
of its busy intervals over the window.  ``idle_share.read`` and
``idle_share.scan`` both read it: the one in the cells judged by a read
tail, the other in the scan cell."""


def read(rec):
    if not rec.get("window_s"):
        return None
    return (1.0 - rec["busy_s"] / rec["window_s"]) * 100.0
