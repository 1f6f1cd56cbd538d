"""segment_padding_waste (%): padding rows the device computed to fill a
power-of-two batch, over all rows it computed, in the window."""


def read(r):
    pad = r.delta("device.pad_rows")
    real = r.delta("device.stacked_rows")
    if pad is None or real is None or pad + real <= 0:
        return None
    return 100.0 * pad / (pad + real)
