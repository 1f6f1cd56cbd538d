"""native_busy_share (%): busy seconds of the native workers (Thread_2)
summed, over workers x window."""


def read(r):
    busy = r.delta("loop.native_busy_s")
    span = r.delta("time")
    workers = r.snaps["end"].get("loop.native_workers")
    if busy is None or not span or not workers:
        return None
    return 100.0 * busy / (workers * span)
