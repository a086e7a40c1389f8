"""Host time of a scheduler iteration in which no slot prefills (one
width-1 program run): total over count, for the window's iterations."""


def read(run):
    ts = [s.t1 - s.t0 for s in run.window_steps() if not s.mixed]
    return 1e3 * sum(ts) / len(ts) if ts else None
