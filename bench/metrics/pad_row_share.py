"""Share of the rows the step programs computed (num_slots x width per
device call) that fed no token, over the window's calls. Counted from the
scheduler's slots, lengths and prefill state around each step."""


def read(run):
    calls = run.window_calls()
    rows = sum((len(c.slots) + c.n_empty) * c.width for c in calls)
    fed = sum(n for c in calls for _, n, _ in c.slots)
    return 100.0 * (1.0 - fed / rows) if rows else None
