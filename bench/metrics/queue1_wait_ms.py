"""queue1_wait_ms (ms): mean time an entity waited on Queue_1, from its
put (at launch or by Thread_3 after a reply) to a native worker taking
it, one wait per hop (the engine's ``queue1`` wait), over the window.
Read beside the profiler trace: silent where no device trace was
reduced."""


def read(r):
    if r.trace is None:
        return None
    s = r.delta("util.trace.waits.queue1.s")
    n = r.delta("util.trace.waits.queue1.n")
    return 1e3 * s / n if s is not None and n else None
