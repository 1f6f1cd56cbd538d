"""model_tokens_per_call (tokens/call): the model op's prefill and
decode tokens (``model_prefill_tokens`` + ``model_decode_tokens``, the
real entities' tokens, not padding) over its compiled calls
(``model_calls``: one prefill and the decode steps of each group), all
over the window.  Read beside the profiler trace: silent where no device
trace was reduced."""


def read(r):
    if r.trace is None:
        return None
    pre = r.delta("util.trace.counts.model_prefill_tokens")
    dec = r.delta("util.trace.counts.model_decode_tokens")
    calls = r.delta("util.trace.counts.model_calls")
    if pre is None or dec is None or not calls:
        return None
    return (pre + dec) / calls
