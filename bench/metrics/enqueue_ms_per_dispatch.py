"""Engine: host milliseconds to issue one prefill, chunk, decode or
verify dispatch, the mean of the program's ``engine.enqueue`` spans over
the whole window. It grows when the runtime's queue of programs in
flight is full and issuing blocks."""


def read(run):
    durs = [s["dur"] for s in run.spans if s["name"] == "engine.enqueue"]
    if not durs:
        return None
    return sum(durs) / 1e3 / len(durs)
