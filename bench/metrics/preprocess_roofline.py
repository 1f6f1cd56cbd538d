"""preprocess_roofline (%): least time of resize -> crop -> normalize for
the entities the device ran in the traced window, over the device's busy
time there (``bench/counts.py``)."""
from bench.counts import roofline_share


def read(r):
    return roofline_share(r, "preprocess")
