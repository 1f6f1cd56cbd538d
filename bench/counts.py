"""Operations and bytes each kernel's work needs, from its shapes alone.

The counts describe the work, not an implementation: bytes are one read
of the input and one write of the output, operations are those of the
textbook algorithm.  A kernel's least time on a chip is the larger of
operations over the peak rate and bytes over the memory bandwidth
(``peaks.json``); a roofline share is that least time over the time the
devices were busy, summed over the devices.
"""
from __future__ import annotations

import json
import math
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str, path: str = PEAKS) -> dict:
    """Peak rates of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]


def image_bytes(shape, itemsize: int = 4) -> int:
    return math.prod(shape) * itemsize


def preprocess(in_shape, out_shape, itemsize: int = 4) -> tuple[int, int]:
    """(operations, bytes) of resize -> crop -> normalize from an (H, W, C)
    image to its (Hc, Wc, C) crop: separable bilinear interpolation, two
    taps (a multiply and an add each) per axis, rows first over the
    cropped rows only, then the affine normalize (a subtract and a
    divide) per output element."""
    hi, wi, c = in_shape
    hc, wc, _ = out_shape
    ops = 4 * hc * wi * c + 4 * hc * wc * c + 2 * hc * wc * c
    return ops, image_bytes(in_shape, itemsize) + image_bytes(out_shape,
                                                              itemsize)


def blur(shape, ksize: int, itemsize: int = 4) -> tuple[int, int]:
    """(operations, bytes) of a separable Gaussian blur: K taps (a
    multiply and an add each) per axis per element."""
    h, w, c = shape
    return 2 * 2 * ksize * h * w * c, 2 * image_bytes(shape, itemsize)


def least_time_s(ops: int, nbytes: int, peak: dict) -> tuple[float, str]:
    """Least seconds on one chip and the bound that sets it."""
    compute = ops / peak["flops_per_s"]
    memory = nbytes / peak["hbm_bytes_per_s"]
    return (memory, "bytes") if memory >= compute else (compute, "ops")


def kernel_work(kernel: str, ops_list: list[dict],
                in_shape) -> tuple[int, int] | None:
    """(operations, bytes) per entity of ``kernel`` in a query whose
    operations list is ``ops_list``, or None where the query does not do
    that kernel's work: ``preprocess`` for exactly resize -> crop ->
    normalize, ``blur`` for exactly one blur."""
    names = [o["type"] if o["type"] not in ("remote", "udf")
             else o["options"]["id"] for o in ops_list]
    if kernel == "preprocess" and names == ["resize", "crop", "normalize"]:
        rs, cr, _ = ops_list
        res_h, res_w = rs["height"], rs["width"]
        out = (min(cr["height"], res_h), min(cr["width"], res_w), in_shape[2])
        return preprocess(in_shape, out)
    if kernel == "blur" and names == ["blur"]:
        opts = ops_list[0] if ops_list[0]["type"] == "blur" \
            else ops_list[0]["options"]
        return blur(in_shape, opts.get("ksize", 5))
    return None


def roofline_share(readings, kernel: str):
    """Percent: the least time on one chip of ``kernel``'s work for every
    entity the devices ran in the traced window, over the busy time of
    all the devices there: the reduced trace's ``busy_s`` is the mean
    over its ``devices``.  None where the cell's queries do not do that
    kernel's work, or the trace or the chip's peaks are missing."""
    if readings.trace is None or readings.peaks is None:
        return None
    works = [kernel_work(kernel, ops, readings.image_shape)
             for ops in readings.cell.traffic["queries"].values()]
    if not works or any(w is None for w in works) or len(set(works)) != 1:
        return None
    entities = readings.delta("device.entities_run", span="trace")
    busy = readings.trace["busy_s"] * readings.trace["devices"]
    if not entities or busy <= 0:
        return None
    least, _ = least_time_s(*works[0], readings.peaks)
    return 100.0 * entities * least / busy
