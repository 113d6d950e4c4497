"""Engine: host-blocking device-to-host transfers per generated token,
from the engines' counters over the whole window."""


def read(run):
    e = run.counters["engine"]
    if not e.get("tokens_generated"):
        return None
    return e["host_blocks"] / e["tokens_generated"]
