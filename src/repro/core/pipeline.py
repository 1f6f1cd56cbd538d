"""Operation pipeline representation + compiled op programs.

Beyond-paper optimization (ARCHITECTURE.md, ``fuse_native``): VDMS-Async
executes pipeline operations one at a time; here, maximal runs of native
ops are jit-fused into a single compiled callable, cached per
(chain-signature, input-shape).  One dispatch replaces N, and XLA fuses
the elementwise stages.  The same cache holds the one-op programs the
remote servers run (:func:`run_compiled`).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import threading
from typing import Any

import jax
import numpy as np

from repro.visual import facedetect
from repro.visual.ops import NATIVE_OPS

# compound vision UDFs shipped with the system (run locally when an op is
# tagged native, or on a remote server / UDF process otherwise)
BUILTIN_UDFS = {
    "facedetect_box": facedetect.facedetect_box,
    "facedetect_mask": facedetect.facedetect_mask,
    "manipulation": facedetect.facedetect_manipulation,
    "activityrecognition": facedetect.activity_recognition,
}
# builtins that read a traced value on the host (``int(device_get(..))``)
# and so run eagerly only
UNTRACEABLE_UDFS = frozenset({"activityrecognition"})


@dataclasses.dataclass(frozen=True)
class Operation:
    name: str
    params: tuple   # sorted tuple of (key, value) pairs — hashable
    where: str      # "native" | "udf" | "remote"
    url: str = ""   # remote endpoint (plug-and-play, paper section 4.2)
    port: int = 0   # UDF message-queue port (paper section 4.1)

    @property
    def kwargs(self) -> dict:
        return dict(self.params)

    @property
    def is_native(self) -> bool:
        return self.where == "native"


def _frozen(value):
    """A UDF's JSON list option (token ids, say) as a tuple, so that the
    operation stays hashable: the device backend keys a group by it."""
    if isinstance(value, (list, tuple)):
        return tuple(_frozen(v) for v in value)
    return value


def make_op(name: str, params: dict | None = None, where: str = "native",
            url: str = "", port: int = 0) -> Operation:
    params = params or {}
    return Operation(name=name, params=tuple(sorted(params.items())),
                     where=where, url=url, port=port)


def parse_operations(op_list: list[dict]) -> list[Operation]:
    """Parse the query JSON operations array (paper Figs 3/5/8).

    Native entry:  {"type": "resize", "width": 400, "height": 500}
    UDF entry:     {"type": "udf", "port": 5555, "options": {"id": "blur", ...}}
    Remote entry:  {"type": "remote", "url": "http://...", "options": {...}}
    """
    out = []
    for entry in op_list:
        e = dict(entry)
        typ = e.pop("type")
        if typ == "udf":
            opts = {k: _frozen(v) for k, v in e.pop("options", {}).items()}
            name = opts.pop("id")
            out.append(make_op(name, opts, where="udf", port=e.get("port", 0)))
        elif typ == "remote":
            opts = dict(e.pop("options", {}))
            name = opts.pop("id")
            out.append(make_op(name, opts, where="remote", url=e.get("url", "")))
        else:
            out.append(make_op(typ, e, where="native"))
    return out


def _local_op(name: str):
    """The function of a locally known op: the native table first, then
    the builtin UDFs; None for any other name (a user UDF)."""
    fn = NATIVE_OPS.get(name)
    return fn if fn is not None else BUILTIN_UDFS.get(name)


def run_op(op: Operation, img):
    """Execute one op locally (native table first, then builtin UDFs).
    Video entities (T,H,W,C) are processed frame-by-frame — ops stay
    image-level like the paper's OpenCV operations."""
    if getattr(img, "ndim", 3) == 4:
        import numpy as _np
        frames = [run_op(op, img[t]) for t in range(img.shape[0])]
        return _np.stack([_np.asarray(f) for f in frames])
    fn = _local_op(op.name)
    if fn is None:
        from repro.core.udf import get_udf
        fn = get_udf(op.name)
    return fn(img, **op.kwargs)


# ------------------------------------------------------ compiled programs
@functools.lru_cache(maxsize=256)
def _fused_chain(chain: tuple, shape: tuple, dtype_str: str):
    """jit-compile a run of locally known ops, ``((name, params), ...)``,
    as one callable."""
    fns = []
    for name, params in chain:
        fn = _local_op(name)
        if fn is None:
            raise KeyError(f"unknown local op {name!r}")
        fns.append((fn, dict(params)))

    def chained(img):
        for fn, kwargs in fns:
            img = fn(img, **kwargs)
        return img

    return jax.jit(chained)


# held only while the cache is looked up and a (lazy) jit wrapper built,
# never across a trace or a dispatch: two threads that miss at once then
# build one program, not two
_build_lock = threading.Lock()


def _run_chain(ops, img):
    arr = jax.numpy.asarray(img)      # one upload for the whole program
    key = tuple((o.name, o.params) for o in ops)
    with _build_lock:
        fn = _fused_chain(key, arr.shape, str(arr.dtype))
    return fn(arr)


def compilable(op: Operation, img) -> bool:
    """Whether ``op`` on ``img`` runs as one compiled program: an image
    entity (``ndim == 3``), an op of the native table or a traceable
    builtin UDF, and hashable params.  User UDFs (arbitrary code),
    untraceable builtins and video entities run eagerly (:func:`run_op`)."""
    if getattr(img, "ndim", None) != 3:
        return False
    if _local_op(op.name) is None or op.name in UNTRACEABLE_UDFS:
        return False
    try:
        hash(op.params)
    except TypeError:
        return False
    return True


def run_compiled(op: Operation, img):
    """Run one op as its cached compiled program (one per op name,
    params, input shape and dtype); the caller checks
    :func:`compilable` first."""
    return _run_chain((op,), img)


def run_native_chain(ops: list[Operation], img, fuse: bool = True):
    """Execute a run of native ops; ``fuse=False`` reproduces the paper's
    op-at-a-time behaviour (the faithful baseline).  Fusion applies to
    image entities; video falls back to the per-op frame loop."""
    if not fuse or getattr(img, "ndim", 3) == 4:
        for op in ops:
            img = run_op(op, img)
        return img
    return _run_chain(ops, img)
