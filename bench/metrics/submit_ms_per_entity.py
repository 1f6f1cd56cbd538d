"""submit_ms_per_entity (ms): the client's thread in ``submit()`` (the
engine's ``submit`` span: parse, plan, phase-0 expansion, admission and
the Queue_1 put) over the entities the planner fanned out
(``entities_planned``), both counted over the window.  Read beside the
profiler trace that shows the same spans: silent where no device trace
was reduced."""


def read(r):
    if r.trace is None:
        return None
    s = r.delta("util.trace.spans.submit.s")
    n = r.delta("util.trace.counts.entities_planned")
    return 1e3 * s / n if s is not None and n else None
