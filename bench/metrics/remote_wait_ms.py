"""remote_wait_ms (ms): mean time a request waited in a remote server's
inbox, from dispatch to the server taking it (the engine's
``remote_inbox`` wait), over the window.  Read beside the profiler
trace: silent where no device trace was reduced."""


def read(r):
    if r.trace is None:
        return None
    s = r.delta("util.trace.waits.remote_inbox.s")
    n = r.delta("util.trace.waits.remote_inbox.n")
    return 1e3 * s / n if s is not None and n else None
