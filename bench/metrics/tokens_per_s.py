"""Output tokens over time, across the window's whole scheduler iterations:
from the end of the last iteration that began before the window opened to
the end of the last one that began before it closed, the tokens emitted in
between over that time. Whole iterations, so the count does not move by a
step's tokens with where the window's edges fall."""


def read(run):
    a = max((s.t1 for s in run.steps if s.t0 < run.open_t),
            default=run.open_t)
    b = max((s.t1 for s in run.steps if s.t0 < run.close_t), default=None)
    if b is None or b <= a:
        return None
    n = sum(1 for t in run.tracks for x in t.times if a < x <= b)
    return n / (b - a)
