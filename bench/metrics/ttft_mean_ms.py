"""Mean, over every request due in the window, of its first token's time
minus the time it was due (open loop): queueing and prefill together. The
window holds some tens of requests at the rates the engine sustains, too
few for a tail; the detail file gives the count (samples.ttft). A request
with no first token makes the mean infinite; the value is then reported as
1e9 ms."""
import math


def read(run):
    due = [t for t in run.tracks
           if t.due is not None and run.in_window(t.due)]
    if not due:
        return None
    ttft = [(t.times[0] - t.due) * 1e3 if t.times else math.inf for t in due]
    v = sum(ttft) / len(ttft)
    return 1e9 if math.isinf(v) else v
