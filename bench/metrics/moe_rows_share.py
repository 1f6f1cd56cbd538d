"""moe_rows_share (%): the rows the model's MoE layers ran through their
held experts' gather, grouped matmuls and scatter
(``model_moe_rows``), over the token-to-expert assignments of the same
calls (``model_moe_assignments``: tokens x top-k x MoE layers, padded
faces included on both), over the window.  100 where every layer runs
all T x K rows.  Read beside the profiler trace: silent where no device
trace was reduced, where the assignments did not move, or where the
program keeps no such counts."""


def read(r):
    if r.trace is None:
        return None
    rows = r.delta("util.trace.counts.model_moe_rows")
    assigned = r.delta("util.trace.counts.model_moe_assignments")
    if rows is None or not assigned:
        return None
    return 100.0 * rows / assigned
