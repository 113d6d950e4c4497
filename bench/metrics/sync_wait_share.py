"""Engine: percent of the scheduler's step time spent blocked in the
harvest's ``device_get``, the program's ``engine.sync`` spans over its
``step`` spans, over the whole window."""


def read(run):
    steps = sum(s["dur"] for s in run.spans if s["name"] == "step")
    if not steps:
        return None
    sync = sum(s["dur"] for s in run.spans if s["name"] == "engine.sync")
    return 100.0 * sync / steps
