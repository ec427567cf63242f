"""Mean host milliseconds per GET call: the call's span minus the device
busy time inside it (padding, syncs, the lease tick, lane merges)."""


def read(rec):
    s = rec.get("spans", {}).get("call.get")
    if not s or not s["count"]:
        return None
    return (s["seconds"] - s["busy_s"]) / s["count"] * 1e3
