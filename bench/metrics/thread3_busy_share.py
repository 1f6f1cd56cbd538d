"""thread3_busy_share (%): seconds Thread_3 (the event loop's dispatch and
reply thread) was busy, over the window."""


def read(r):
    busy = r.delta("loop.t3_busy_s")
    span = r.delta("time")
    return 100.0 * busy / span if busy is not None and span else None
