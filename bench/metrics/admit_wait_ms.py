"""Mean, over every request due in the window while the profiler was not
running, of its first admission into a slot (`Request.admit_t`, stamped by
the engine) minus the time it was due: the part of its time to first
token spent before it first ran, which is the scheduler iteration under
way when it fell due and the queue. Requests due while the profiler ran
are left out: stopping it stalls the drive loop for seconds (9.4 s in a
chat run on a v5e), and the requests due then wait for it. A program whose
requests carry no admission stamp reads nothing; a request never admitted
makes the mean infinite, reported as 1e9 ms as in ttft_mean_ms."""
import math


def read(run):
    a, b = run.trace_span or (math.inf, math.inf)
    due = [t for t in run.tracks if t.due is not None
           and run.in_window(t.due) and not a <= t.due < b]
    if not due or not hasattr(due[0].request, "admit_t"):
        return None
    waits = [math.inf if t.request.admit_t is None
             else (t.request.admit_t - t.due) * 1e3 for t in due]
    v = sum(waits) / len(waits)
    return 1e9 if math.isinf(v) else v
