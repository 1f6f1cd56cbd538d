"""remote_compiled_share (%): the share of the remote servers'
entity-ops that ran as one cached compiled program (the engine's
``remote_compiled`` count) rather than eagerly (``remote_eager``), over
the window.  Read beside the profiler trace: silent where no device
trace was reduced, and where the program has no such counts."""


def read(r):
    if r.trace is None:
        return None
    compiled = r.delta("util.trace.counts.remote_compiled")
    eager = r.delta("util.trace.counts.remote_eager")
    if compiled is None or eager is None or compiled + eager == 0:
        return None
    return 100.0 * compiled / (compiled + eager)
