"""segment_host_ms_per_entity (ms/entity): the device worker's host
work per entity it ran: staging (``device_stage``: stack, pad,
``device_put``, dispatch), the fetch (``device_fetch``: ``device_get``
and the split into rows) and the replies (``device_deliver``), over
``device.entities_run``, all over the window.  Read beside the profiler
trace: silent where no device trace was reduced."""

SPANS = ("device_stage", "device_fetch", "device_deliver")


def read(r):
    if r.trace is None:
        return None
    parts = [r.delta(f"util.trace.spans.{s}.s") for s in SPANS]
    n = r.delta("device.entities_run")
    if any(p is None for p in parts) or not n:
        return None
    return 1e3 * sum(parts) / n
