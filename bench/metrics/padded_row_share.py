"""Scheduler: share of the rows in admitted waves that were padding
(the batch bucket's empty rows and experts with no rows in a banked
wave), from the engines' counters over the whole window."""


def read(run):
    e = run.counters["engine"]
    total = e.get("rows_served", 0) + e.get("rows_padded", 0)
    if not total:
        return None
    return 100.0 * e["rows_padded"] / total
