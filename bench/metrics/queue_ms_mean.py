"""Scheduler: mean milliseconds a request waited in the admission queue
(submit to admit, stalls excluded), from the ``queue_ms`` histogram's
sum and count over the whole window."""


def read(run):
    n = run.counters["queue_ms_count"]
    if not n:
        return None
    return run.counters["queue_ms_sum"] / n
