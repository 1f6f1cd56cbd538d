"""queue2_wait_ms (ms): mean time an item waited in Thread_3's inbox
(Queue_2: dispatches, and device and remote replies) before Thread_3
took it (the engine's ``queue2`` wait), over the window.  Read beside
the profiler trace: silent where no device trace was reduced."""


def read(r):
    if r.trace is None:
        return None
    s = r.delta("util.trace.waits.queue2.s")
    n = r.delta("util.trace.waits.queue2.n")
    return 1e3 * s / n if s is not None and n else None
