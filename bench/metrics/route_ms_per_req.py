"""Router: host milliseconds per routed request, from the program's
``route`` spans (scheduler, around ``Router.route``) over the whole
window. The call blocks on the device scores, so this is the time a
submit spends routing."""


def read(run):
    spans = [s for s in run.spans if s["name"] == "route"]
    rows = sum(s["args"].get("rows", 0) for s in spans)
    if not rows:
        return None
    return sum(s["dur"] for s in spans) / 1e3 / rows
