"""The reader of ``moe_rows_share``, put through the same checks as the
model layer's other counter readers (``test_bench_model_op.py``)."""
from __future__ import annotations

import pytest

from test_bench_model_op import _read, _snaps, _t

ROWS = _t("counts", "model_moe_rows")
ASSIGNED = _t("counts", "model_moe_assignments")
# one group of 32 faces: a prefill of 3,360 tokens at its 5,120-row tier
# and three decode steps of 256 tokens at 384 rows, over 26 MoE layers,
# against 6 assignments a token
DELTAS = {ROWS: (1000, 1000 + 26 * (5120 + 3 * 384)),
          ASSIGNED: (6000, 6000 + 26 * 6 * (3360 + 3 * 256))}
VALUE = 100.0 * (5120 + 3 * 384) / (6 * (3360 + 3 * 256))


@pytest.mark.parametrize("check", ["arithmetic", "still_denominator",
                                   "no_program_record", "no_trace"])
def test_moe_rows_share_reader(check):
    if check == "arithmetic":
        assert _read("moe_rows_share", _snaps(**DELTAS)) == \
            pytest.approx(VALUE)
        assert _read("moe_rows_share", _snaps(**DELTAS),
                     cell="lfw_device.blur") == pytest.approx(VALUE)
    elif check == "still_denominator":
        a, _ = DELTAS[ASSIGNED]
        still = {**DELTAS, ASSIGNED: (a, a)}
        assert _read("moe_rows_share", _snaps(**still)) is None
    elif check == "no_program_record":
        # the parent of this counter's program gives nothing, never 0
        assert _read("moe_rows_share", _snaps()) is None
        assert _read("moe_rows_share", _snaps(**{ROWS: DELTAS[ROWS]})) \
            is None
    else:
        assert _read("moe_rows_share", _snaps(**DELTAS), trace=None) is None
