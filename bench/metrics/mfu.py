"""Model step: the share of the chip's bf16 peak spent on useful model
work in the profiled seconds. Work is counted from shapes (the cell's
family, ``bench/families/``): every row prefilled (its real prompt
tokens and the head at its last position) and every token decoded (all
layers, its attention over the keys it sees, the head), as the engines'
counters give them over the profiled seconds, over the seconds times
the peak. Idle time counts against it."""
from bench import flops


def read(run):
    t, c = run.trace, run.trace_counters
    if t is None or c is None or not t.window_s or not run.served:
        return None
    e = c["engine"]
    rows = e.get("rows_served", 0)
    decoded = run.decoded_in_trace()
    pre, keys = flops.served_means(run.family, run.arch, run.served)
    work = rows * pre + decoded * run.family.decode_flops(run.arch, keys)
    if not work:
        return None
    return 100.0 * work / (t.window_s * run.peaks["peak_flops_bf16"])
