"""compiles_per_entity (compiles/entity): XLA compiles in the process
(persistent-cache loads included: the engine's ``compiles`` count) over
the entities the engine finished or failed (``entities_done``), both
over the window.  Read beside the profiler trace: silent where no
device trace was reduced."""


def read(r):
    if r.trace is None:
        return None
    c = r.delta("util.trace.counts.compiles")
    n = r.delta("util.trace.counts.entities_done")
    return c / n if c is not None and n else None
