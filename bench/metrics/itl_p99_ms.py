"""99th percentile over every gap between consecutive output tokens of any
request, for gaps that end in the window."""
from bench.run import percentile


def read(run):
    gaps = [(b - a) * 1e3 for t in run.tracks
            for a, b in zip(t.times, t.times[1:]) if run.in_window(b)]
    return percentile(gaps, 99) if gaps else None
