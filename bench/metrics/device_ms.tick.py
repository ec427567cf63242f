"""Device milliseconds of the heartbeat tick program per GET call: the
``jit_histore_tick`` module's device time per ``histore.get`` span."""
from bench.program_trace import per_call


def read(rec):
    v = per_call(rec, "get", lambda r: r["modules"].get(
        "jit_histore_tick", {}).get("device_s"))
    return None if v is None else v * 1e3
