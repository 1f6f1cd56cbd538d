"""model_host_ms_per_entity (ms/entity): the model op's host work per
entity the device worker ran: the span ``model_host`` (stacking, padding
and putting a group's images on the device; fetching and splitting its
log-probabilities) over ``device.entities_run``, over the window.  Read
beside the profiler trace: silent where no device trace was reduced."""


def read(r):
    if r.trace is None:
        return None
    host = r.delta("util.trace.spans.model_host.s")
    n = r.delta("device.entities_run")
    if host is None or not n:
        return None
    return 1e3 * host / n
