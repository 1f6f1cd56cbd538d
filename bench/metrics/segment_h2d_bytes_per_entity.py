"""segment_h2d_bytes_per_entity (bytes/entity): host-to-device bytes the
device backend moved over the entities it ran, both counted over the
window."""


def read(r):
    h2d = r.delta("device.h2d_bytes")
    n = r.delta("device.entities_run")
    return h2d / n if h2d is not None and n else None
