"""segment_wait_ms (ms): mean time from an entity's submit to the device
backend until its micro-batch starts staging: its wait in the device
inbox (``offload_inbox``) plus the group's collection hold
(``device_collect``, which the engine counts once per member), over the
entities that entered the inbox in the window.  Read beside the
profiler trace: silent where no device trace was reduced."""


def read(r):
    if r.trace is None:
        return None
    inbox = r.delta("util.trace.waits.offload_inbox.s")
    hold = r.delta("util.trace.spans.device_collect.s")
    n = r.delta("util.trace.waits.offload_inbox.n")
    if inbox is None or hold is None or not n:
        return None
    return 1e3 * (inbox + hold) / n
