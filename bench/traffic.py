"""The one traffic generator: a mix file's parameters -> each client's
stream of queries, drawn from the seed.

A mix file (``bench/traffic/<name>.json``) holds:

- ``loop``: ``"closed"`` (each client sends its next query when the
  last one has returned), the only kind this generator makes;
- ``clients``: how many clients send at once;
- ``queries``: name -> the query's ``operations`` list (VDMS JSON);
- ``age_window``: ``[least, most]`` years an age window spans;
- ``check_share``: the share of queries whose pixels are compared with
  the reference once the window has closed.

Every query is a ``FindImage`` on one category and an age window.  The
templates and window widths follow a schedule that the mix alone fixes,
so every seed asks the same work of a window:

- client ``c``'s ``k``-th query runs template ``(c + k) mod T`` (the
  templates in name order), so any ``T`` queries in a row of one client
  hold every template once, and clients at the same step run different
  templates;
- its window width is the ``((k // T + c) mod W)``-th of the ``W``
  widths: one width per cycle of templates, each client starting at its
  own, so ``T * W`` queries in a row hold every (template, width) pair
  once.

The seed draws what the work is done on: the category, the first age
of each window, and which queries are compared.
"""
from __future__ import annotations

import dataclasses

import numpy as np

LOOPS = ("closed",)


@dataclasses.dataclass(frozen=True)
class Query:
    template: str
    category: str
    age_lo: int
    age_hi: int
    operations: list
    checked: bool

    def json(self) -> list[dict]:
        return [{"FindImage": {
            "constraints": {"category": ["==", self.category],
                            "age": [">=", self.age_lo, "<=", self.age_hi]},
            "operations": self.operations}}]


def by_index(lo: int, hi: int, operations: list) -> list[dict]:
    """A query selecting the faces of dataset index ``lo..hi`` (warm-up)."""
    return [{"FindImage": {"constraints": {"idx": [">=", lo, "<=", hi]},
                           "operations": operations}}]


def schedule(traffic: dict, client: int, k: int) -> tuple[str, int]:
    """(template, window width) of client ``client``'s ``k``-th query."""
    names = sorted(traffic["queries"])
    lo_w, hi_w = traffic["age_window"]
    n_widths = hi_w - lo_w + 1
    t = len(names)
    return (names[(client + k) % t],
            lo_w + (k // t + client) % n_widths)


def validate(traffic: dict) -> None:
    """Refuse a mix this generator cannot make."""
    if traffic["loop"] not in LOOPS:
        raise ValueError(f"loop {traffic['loop']!r}: this generator makes "
                         f"{LOOPS}")


def stream(traffic: dict, collection: dict, seed: int, client: int):
    """Endless queries of one client."""
    validate(traffic)
    rng = np.random.default_rng([seed, 2, client])
    cats = collection["categories"]
    a0, a1 = collection["age_min"], collection["age_max"]
    share = traffic["check_share"]
    k = 0
    while True:
        name, width = schedule(traffic, client, k)
        k += 1
        first = int(rng.integers(a0, a1 - width + 2))
        yield Query(template=name,
                    category=cats[int(rng.integers(len(cats)))],
                    age_lo=first, age_hi=first + width - 1,
                    operations=traffic["queries"][name],
                    checked=bool(rng.random() < share))
