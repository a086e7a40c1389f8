"""Host time of a scheduler iteration with a prefilling slot (the
width-prefill_chunk program, then width 1 for the decoding slots): total
over count, for the window's iterations."""


def read(run):
    ts = [s.t1 - s.t0 for s in run.window_steps() if s.mixed]
    return 1e3 * sum(ts) / len(ts) if ts else None
