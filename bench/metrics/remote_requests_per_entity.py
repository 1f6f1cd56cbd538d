"""remote_requests_per_entity (requests/entity): requests the remote pool
dispatched over the entity-operations its servers processed, in the
window."""


def read(r):
    sent = r.delta("util.remote_dispatched")
    done = r.delta("util.remote_processed")
    return sent / done if sent is not None and done else None
