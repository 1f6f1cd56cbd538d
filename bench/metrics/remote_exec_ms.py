"""remote_exec_ms (ms): the remote servers' real work per entity-op:
running the op, as a cached compiled program or eagerly, and
``block_until_ready`` (the engine's ``remote_exec`` span, the modelled
network sleep left out) over the entity-operations the servers
processed, both over the window.  Read beside the profiler
trace: silent where no device trace was reduced."""


def read(r):
    if r.trace is None:
        return None
    s = r.delta("util.trace.spans.remote_exec.s")
    n = r.delta("util.remote_processed")
    return 1e3 * s / n if s is not None and n else None
