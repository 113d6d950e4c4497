"""Model step: the engine's step programs' device time against the
least time the chip could take for their work in the profiled seconds.
Each prefill dispatch reads, for every dispatched expert, the weights
its share of the dispatch's prompt tokens needs, and writes the keys and
values of its rows' prompts; each decode dispatch reads the weights for
its share of the decoded tokens and the cached keys and values its rows
attend over (counts from the cell's family, ``bench/families/``). The
least time of each phase is the larger of its bytes over bandwidth and
its operations over peak, and the two phases' least times add.
Dispatches and rows come from the engines' counters over the profiled
seconds, decoded tokens from ``View.decoded_in_trace``, the device time
from the trace's step programs (``xtrace.STEP_MODULES``)."""
from bench import flops


def read(run):
    t, c = run.trace, run.trace_counters
    if t is None or c is None or not run.served:
        return None
    dev = t.seconds.get("step", 0.0)
    if not dev:
        return None
    fam, a, e = run.family, run.arch, c["engine"]
    bw, peak = run.peaks["hbm_bytes_per_s"], run.peaks["peak_flops_bf16"]
    kv = fam.kv_bytes_per_token(a)
    pre, keys = flops.served_means(fam, a, run.served)
    prompt = sum(s.prompt_len for s in run.served) / len(run.served)
    rows = e.get("rows_served", 0)
    decoded = run.decoded_in_trace()
    prefills, decodes = e.get("prefill_calls", 0), e.get("decode_steps", 0)

    def weights(tokens, dispatches):
        # what every dispatched expert reads, for the tokens of its share
        # of one dispatch
        share = tokens / (dispatches * run.experts_per_dispatch) \
            if dispatches else 0.0
        return run.experts_per_dispatch * fam.weight_bytes_read(a, share)

    least = (max((prefills * weights(rows * prompt, prefills)
                  + rows * prompt * kv) / bw, rows * pre / peak)
             + max((decodes * weights(decoded, decodes)
                    + decoded * keys * kv) / bw,
                   decoded * fam.decode_flops(a, keys) / peak))
    if not least:
        return None
    return 100.0 * least / dev
