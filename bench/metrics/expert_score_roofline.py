"""Routing kernels: the fused AE-bank score kernel's device time against
the least time its work needs (``bench.flops.expert_score_cost``): the
fingerprints scored in the profiled seconds (routed minus route-cache
hits, from the router's counters) under every expert, each call reading
the bank's weights once. The device time is the sum of the kernel's
own events in the trace."""
from bench import flops


def read(run):
    t, c = run.trace, run.trace_counters
    if t is None or c is None:
        return None
    calls = t.kernel_calls.get("expert_score", [])
    rows = c["router"].get("routed", 0) - c["router"].get("cache_hits", 0)
    dev = sum(calls)
    if not calls or not dev or rows <= 0:
        return None
    ops, nbytes = flops.expert_score_cost(rows, run.experts, len(calls))
    least = max(nbytes / run.peaks["hbm_bytes_per_s"],
                ops / run.peaks["peak_flops_bf16"])
    return 100.0 * least / dev
