"""Router: host milliseconds per routed request spent blocked in the
router's device-to-host transfers, from the program's ``route.wait``
spans (each blocking transfer inside ``Router.route``) over the rows of
its ``route`` spans, over the whole window. The rest of
``route_ms_per_req`` is routing's own host work and dispatch."""


def read(run):
    waits = [s["dur"] for s in run.spans if s["name"] == "route.wait"]
    rows = sum(s["args"].get("rows", 0) for s in run.spans
               if s["name"] == "route")
    if not waits or not rows:
        return None
    return sum(waits) / 1e3 / rows
