"""Router: engine dispatches still uncovered by a completed sync when a
routing call began, the ``ahead`` argument of the program's ``route``
spans, averaged over the window's routing calls. On one chip's in-order
stream a blocking transfer in the router waits behind at most these."""


def read(run):
    ahead = [s["args"]["ahead"] for s in run.spans
             if s["name"] == "route" and "ahead" in s["args"]]
    if not ahead:
        return None
    return sum(ahead) / len(ahead)
