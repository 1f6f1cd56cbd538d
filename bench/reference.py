"""Plain reference of the query operations, independent of the program.

Each operation of a query's ``operations`` list, written out in
straightforward ``jax.numpy`` from its published meaning (OpenCV-style
crop, bilinear resize, BT.601 grayscale, separable Gaussian blur with
reflect-101 borders, binary threshold, affine normalize, a 5x7 bitmap
caption, and the toy face detector's box / mask / keep-the-face UDFs).
It imports nothing of ``repro``.

``reference_fn(ops, dtype)`` returns a function of one (H, W, C) image;
``run(ops, images, device, dtype)`` applies it over a batch on one
device, jitted and vmapped.  The benchmark calls it with float32 on the
host CPU to decide ``correct``; the control (``bench/control.py`` and the
tests) calls it with bfloat16, the next precision below the float32 the
configurations state.

An op that is not one of these (``OPS``) is a configuration's own op,
whose reference is the file ``references/<op>.py`` of the benchmark's
directory.  Such a file imports nothing of ``repro`` and gives:

- ``DISCRETE``: whether its output holds a discrete choice;
- ``ON_DEVICE``: whether its reference runs on the cell's first chip
  (a model too large for the host) rather than on the host CPU;
- optionally ``weights(spec, seed) -> dict`` of arrays: the op's data,
  drawn from the seed, which the program is given too;
- ``reference(images, params, *, spec, weights, device, dtype) ->
  np.ndarray``: the op over a batch of inputs, computed in ``dtype``
  under ``jax.default_matmul_precision("highest")`` on ``device``, in
  blocks it chooses so that it fits there.
"""
from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))

# 5x7 glyphs of the caption font (one string per row, '1' = ink)
GLYPHS = {
    "A": ["01110", "10001", "10001", "11111", "10001", "10001", "10001"],
    "B": ["11110", "10001", "11110", "10001", "10001", "10001", "11110"],
    "C": ["01111", "10000", "10000", "10000", "10000", "10000", "01111"],
    "D": ["11110", "10001", "10001", "10001", "10001", "10001", "11110"],
    "E": ["11111", "10000", "11110", "10000", "10000", "10000", "11111"],
    "F": ["11111", "10000", "11110", "10000", "10000", "10000", "10000"],
    "G": ["01111", "10000", "10000", "10011", "10001", "10001", "01111"],
    "H": ["10001", "10001", "11111", "10001", "10001", "10001", "10001"],
    "I": ["11111", "00100", "00100", "00100", "00100", "00100", "11111"],
    "J": ["11111", "00010", "00010", "00010", "10010", "10010", "01100"],
    "K": ["10001", "10010", "11100", "10010", "10001", "10001", "10001"],
    "L": ["10000", "10000", "10000", "10000", "10000", "10000", "11111"],
    "M": ["10001", "11011", "10101", "10101", "10001", "10001", "10001"],
    "N": ["10001", "11001", "10101", "10011", "10001", "10001", "10001"],
    "O": ["01110", "10001", "10001", "10001", "10001", "10001", "01110"],
    "P": ["11110", "10001", "10001", "11110", "10000", "10000", "10000"],
    "Q": ["01110", "10001", "10001", "10001", "10101", "10010", "01101"],
    "R": ["11110", "10001", "10001", "11110", "10010", "10001", "10001"],
    "S": ["01111", "10000", "01110", "00001", "00001", "10001", "01110"],
    "T": ["11111", "00100", "00100", "00100", "00100", "00100", "00100"],
    "U": ["10001", "10001", "10001", "10001", "10001", "10001", "01110"],
    "V": ["10001", "10001", "10001", "10001", "10001", "01010", "00100"],
    "W": ["10001", "10001", "10001", "10101", "10101", "11011", "10001"],
    "X": ["10001", "01010", "00100", "00100", "01010", "10001", "10001"],
    "Y": ["10001", "01010", "00100", "00100", "00100", "00100", "00100"],
    "Z": ["11111", "00010", "00100", "01000", "10000", "10000", "11111"],
    "0": ["01110", "10001", "10011", "10101", "11001", "10001", "01110"],
    "1": ["00100", "01100", "00100", "00100", "00100", "00100", "01110"],
    "2": ["01110", "10001", "00001", "00110", "01000", "10000", "11111"],
    "3": ["11110", "00001", "00001", "01110", "00001", "00001", "11110"],
    "4": ["00010", "00110", "01010", "10010", "11111", "00010", "00010"],
    "5": ["11111", "10000", "11110", "00001", "00001", "10001", "01110"],
    "6": ["01110", "10000", "11110", "10001", "10001", "10001", "01110"],
    "7": ["11111", "00001", "00010", "00100", "01000", "01000", "01000"],
    "8": ["01110", "10001", "10001", "01110", "10001", "10001", "01110"],
    "9": ["01110", "10001", "10001", "01111", "00001", "00001", "01110"],
    " ": ["00000", "00000", "00000", "00000", "00000", "00000", "00000"],
    "-": ["00000", "00000", "00000", "11111", "00000", "00000", "00000"],
    ".": ["00000", "00000", "00000", "00000", "00000", "00100", "00100"],
    ":": ["00000", "00100", "00100", "00000", "00100", "00100", "00000"],
}


# ------------------------------------------------------------ image ops
def crop(img, *, x, y, width, height):
    """Window of the image; a window past the edge is shrunk to the image
    and its start moved inside it."""
    H, W, _ = img.shape
    h, w = min(height, H), min(width, W)
    y0, x0 = max(0, min(y, H - h)), max(0, min(x, W - w))
    return img[y0:y0 + h, x0:x0 + w]


def resize(img, *, width, height, method="bilinear"):
    return jax.image.resize(img, (height, width, img.shape[2]), method=method)


def upsample(img, *, fx=2.0, fy=2.0):
    H, W, _ = img.shape
    return resize(img, width=int(W * fx), height=int(H * fy))


def downsample(img, *, fx=2.0, fy=2.0):
    H, W, _ = img.shape
    return resize(img, width=max(int(W / fx), 1), height=max(int(H / fy), 1))


def grayscale(img):
    w = [img.dtype.type(c) for c in (0.299, 0.587, 0.114)]
    g = img[..., 0] * w[0] + img[..., 1] * w[1] + img[..., 2] * w[2]
    return jnp.repeat(g[..., None], img.shape[-1], axis=-1)


def gaussian_taps(ksize: int, sigma: float) -> np.ndarray:
    """OpenCV's getGaussianKernel: sigma <= 0 means
    0.3 * ((ksize - 1) / 2 - 1) + 0.8."""
    if sigma <= 0:
        sigma = 0.3 * ((ksize - 1) * 0.5 - 1) + 0.8
    x = np.arange(ksize, dtype=np.float64) - (ksize - 1) / 2
    w = np.exp(-x ** 2 / (2 * sigma ** 2))
    return w / w.sum()


def blur(img, *, ksize=5, sigma_x=0.0, sigma_y=0.0):
    """Separable Gaussian blur, rows then columns, reflect-101 borders."""
    ky = gaussian_taps(ksize, sigma_y or sigma_x)
    kx = gaussian_taps(ksize, sigma_x)
    pad = ksize // 2
    H, W, _ = img.shape
    p = jnp.pad(img, ((pad, pad), (0, 0), (0, 0)), mode="reflect")
    out = sum(img.dtype.type(ky[i]) * p[i:i + H] for i in range(ksize))
    p = jnp.pad(out, ((0, 0), (pad, pad), (0, 0)), mode="reflect")
    return sum(img.dtype.type(kx[i]) * p[:, i:i + W] for i in range(ksize))


def threshold(img, *, value=0.5, max_value=1.0):
    return jnp.where(img > value, max_value, 0.0).astype(img.dtype)


def normalize(img, *, mean=0.0, std=1.0):
    return (img - img.dtype.type(mean)) / img.dtype.type(std)


def caption(img, *, text="", x=4, y=4, intensity=1.0):
    """Stamp ``text`` in the 5x7 font at (x, y), one blank column after
    each letter, clipped to the image."""
    cols = [np.pad(np.array([[int(c) for c in row]
                             for row in GLYPHS.get(ch, GLYPHS[" "])],
                            np.float32), ((0, 0), (0, 1)))
            for ch in str(text).upper()]
    ink = np.concatenate(cols, 1) if cols else np.zeros((7, 1), np.float32)
    H, W, _ = img.shape
    h, w = min(ink.shape[0], max(H - y, 0)), min(ink.shape[1], max(W - x, 0))
    if h == 0 or w == 0:
        return img
    m = np.zeros((H, W, 1), np.float32)
    m[y:y + h, x:x + w, 0] = ink[:h, :w]
    m = jnp.asarray(m, img.dtype)
    return img * (1 - m) + m * img.dtype.type(intensity)


# ----------------------------------------------------- toy face detector
def detect_face(img):
    """Centre (cx, cy) and radius of the most face-like 16x16 cell: local
    contrast against an 8x-coarse copy, weighted up on skin tones."""
    H, W, _ = img.shape
    r, g, b = img[..., 0], img[..., 1], img[..., 2]
    skin = (r > g) & (g > b * 0.8) & (r > 0.25) & (r < 0.95)
    gray = jnp.mean(img, axis=-1)
    coarse = jax.image.resize(gray, (max(H // 8, 1), max(W // 8, 1)), "linear")
    local = jnp.abs(gray - jax.image.resize(coarse, (H, W), "linear"))
    score = local * (0.5 + 0.5 * skin.astype(img.dtype))
    grid = jax.image.resize(score, (max(H // 16, 1), max(W // 16, 1)),
                            "linear")
    best = jnp.argmax(grid)
    cy = (best // grid.shape[1]) * 16 + 8
    cx = (best % grid.shape[1]) * 16 + 8
    return cx, cy, min(H, W) // 4


def _grid(img):
    H, W, _ = img.shape
    return jnp.arange(H)[:, None], jnp.arange(W)[None, :]


def facedetect_box(img, **_):
    """The image with a 2-pixel green square of side 2r around the face."""
    cx, cy, r = detect_face(img)
    ys, xs = _grid(img)
    inside = (ys >= cy - r) & (ys < cy + r) & (xs >= cx - r) & (xs < cx + r)
    inner = ((ys >= cy - r + 2) & (ys < cy + r - 2)
             & (xs >= cx - r + 2) & (xs < cx + r - 2))
    green = jnp.asarray([0.0, 1.0, 0.0], img.dtype)
    return jnp.where((inside & ~inner)[..., None], green, img)


def _disk(img, cx, cy, r: int):
    ys, xs = _grid(img)
    return ((ys - cy).astype(jnp.float32) ** 2
            + (xs - cx).astype(jnp.float32) ** 2) <= float(r) ** 2


def facedetect_mask(img, *, r=None, **_):
    """The image with a black disk of radius r over the face."""
    cx, cy, rr = detect_face(img)
    disk = _disk(img, cx, cy, rr if r is None else int(r))
    return jnp.where(disk[..., None], img.dtype.type(0), img)


def manipulation(img, **_):
    """Only the face disk kept, everything else black."""
    cx, cy, r = detect_face(img)
    disk = _disk(img, cx, cy, r)
    return jnp.where(disk[..., None], img, img.dtype.type(0))


OPS = {
    "crop": crop, "resize": resize, "upsample": upsample,
    "downsample": downsample, "grayscale": grayscale, "blur": blur,
    "threshold": threshold, "normalize": normalize, "caption": caption,
    "facedetect_box": facedetect_box, "facedetect_mask": facedetect_mask,
    "manipulation": manipulation,
}
# ops whose output holds a discrete choice (a threshold or a detector's
# argmax); a rounding difference upstream can flip it
DISCRETE = {"threshold", "facedetect_box", "facedetect_mask", "manipulation"}


def op_steps(ops: list[dict]) -> list[tuple[str, dict]]:
    """(name, params) of each entry of a query's operations list:
    ``{"type": name, **params}`` for a native op, ``{"type": "remote" |
    "udf", "options": {"id": name, **params}}`` for the others."""
    steps = []
    for entry in ops:
        e = dict(entry)
        kind = e.pop("type")
        if kind in ("remote", "udf"):
            opts = dict(e.get("options", {}))
            steps.append((opts.pop("id"), opts))
        else:
            steps.append((kind, e))
    return steps


@functools.lru_cache(maxsize=None)
def own_op(name: str, bench_dir: str = BENCH):
    """The module ``references/<name>.py`` of a configuration's own op."""
    from bench.harness import load_module
    return load_module(bench_dir, "references", name)


def is_discrete(ops: list[dict], bench_dir: str = BENCH) -> bool:
    return any(name in DISCRETE if name in OPS
               else own_op(name, bench_dir).DISCRETE
               for name, _ in op_steps(ops))


def reference_fn(ops: list[dict], dtype=jnp.float32):
    """One image (H, W, C) -> the query's output, computed in ``dtype``."""
    steps = op_steps(ops)

    def one(img):
        img = img.astype(dtype)
        for name, params in steps:
            img = OPS[name](img, **params)
        return img
    return one


@functools.lru_cache(maxsize=64)
def _compiled(key: str, dtype_name: str):
    return jax.jit(jax.vmap(reference_fn(json.loads(key),
                                         jnp.dtype(dtype_name))))


def _run_builtin(ops, images, device, dtype, block):
    fn = _compiled(json.dumps(ops, sort_keys=True), jnp.dtype(dtype).name)
    out = []
    with jax.default_device(device):
        for lo in range(0, len(images), block):
            x = jax.device_put(images[lo:lo + block], device)
            out.append(np.asarray(fn(x).astype(jnp.float32)))
    return np.concatenate(out) if out else np.zeros((0,), np.float32)


def run(ops: list[dict], images: np.ndarray, device, dtype=jnp.float32,
        block: int = 32, *, bench_dir: str = BENCH, op_data=None,
        chip=None) -> np.ndarray:
    """The reference over a batch (N, H, W, C): each run of built-in ops
    on ``device``, ``block`` images per call, and each configuration's
    own op by its ``references/<op>.py``, given its spec and weights
    from ``op_data`` (name -> {"spec", "weights"}), on ``chip`` where
    the file says ``ON_DEVICE`` (``device`` where no chip is given)."""
    steps = op_steps(ops)
    x, i = images, 0
    while i < len(ops):
        name, params = steps[i]
        if name in OPS:
            j = i + 1
            while j < len(ops) and steps[j][0] in OPS:
                j += 1
            x = _run_builtin(ops[i:j], x, device, dtype, block)
            i = j
            continue
        ref = own_op(name, bench_dir)
        d = op_data[name]
        x = np.asarray(ref.reference(
            x, params, spec=d["spec"], weights=d["weights"],
            device=(chip or device) if ref.ON_DEVICE else device,
            dtype=dtype))
        i += 1
    return x
