"""Mean host milliseconds per SCAN call: the call's span minus the device
busy time inside it."""


def read(rec):
    s = rec.get("spans", {}).get("call.scan")
    if not s or not s["count"]:
        return None
    return (s["seconds"] - s["busy_s"]) / s["count"] * 1e3
