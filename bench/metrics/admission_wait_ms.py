"""admission_wait_ms (ms): mean time an entity waited in the admission
controller's pending lane, 0 for one admitted at once (the engine's
``admission`` wait), over the window.  Read beside the profiler trace:
silent where no device trace was reduced."""


def read(r):
    if r.trace is None:
        return None
    s = r.delta("util.trace.waits.admission.s")
    n = r.delta("util.trace.waits.admission.n")
    return 1e3 * s / n if s is not None and n else None
