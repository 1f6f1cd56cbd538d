"""kimi_vl_mfu (%): the model FLOPs of the tokens the device ran in the
traced window, over the chip's bf16 peak times the traced window times
the devices.  The FLOPs are counted here from the op's spec
(``configs/<config>.json``'s ``ops.kimi_vl_tag``) and the query's
prompt and answers: per token 2 x the matrix parameters it touches
(attention, the dense MLP or the MoE layer's router, shared experts and
``num_experts_per_tok * experts_held / n_routed_experts`` routed
experts, the image projector for image tokens), attention's QK and PV
at each position's context, and the head only at the positions whose
log-probabilities are used (a prompt's last, each answer's first T - 1).
Silent where no device trace was reduced or no model token ran."""

OP = "kimi_vl_tag"


def _matmul_params(s: dict) -> tuple[int, int]:
    """(dense layer, MoE layer) matrix parameters one token touches."""
    d, h = s["hidden_size"], s["num_attention_heads"]
    r, dn, dr, dv = (s["kv_lora_rank"], s["qk_nope_head_dim"],
                     s["qk_rope_head_dim"], s["v_head_dim"])
    attn = d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d
    expert = 3 * d * s["moe_intermediate_size"]
    routed = (s["num_experts_per_tok"] * s["experts_held"]
              / s["n_routed_experts"])
    moe = (d * s["n_routed_experts"] + s["n_shared_experts"] * expert
           + routed * expert)
    return attn + 3 * d * s["intermediate_size"], attn + moe


def entity_flops(s: dict, image_tokens: int, prompt: int, answers: int,
                 length: int) -> tuple[float, float]:
    """(prefill, decode) FLOPs of one image: ``image_tokens + prompt``
    positions prefilled, ``answers`` x (``length`` - 1) decoded."""
    dense, moe = _matmul_params(s)
    k = s["first_k_dense_replace"]
    per_token = 2 * (k * dense + (s["num_hidden_layers"] - k) * moe)
    h = s["num_attention_heads"]
    qk, dv = s["qk_nope_head_dim"] + s["qk_rope_head_dim"], s["v_head_dim"]
    per_context = 2 * h * (qk + dv) * s["num_hidden_layers"]
    head = 2 * s["hidden_size"] * s["vocab_size"]
    feats = s["patch_merge"] ** 2 * s["patch_size"] ** 2 * 3
    S = image_tokens + prompt
    prefill = (S * per_token + per_context * S * (S + 1) / 2 + head
               + 2 * image_tokens * feats * s["hidden_size"])
    steps = length - 1
    contexts = sum(S + 1 + t for t in range(steps))
    decode = answers * (steps * (per_token + head) + per_context * contexts)
    return prefill, decode


def read(r):
    if r.trace is None or r.peaks is None or r.trace["window_s"] <= 0:
        return None
    spec = r.cell.config.get("ops", {}).get(OP)
    pre = r.delta("util.trace.counts.model_prefill_tokens", span="trace")
    dec = r.delta("util.trace.counts.model_decode_tokens", span="trace")
    if spec is None or pre is None or dec is None or not pre + dec:
        return None
    opts = [o["options"] for ops in r.cell.traffic["queries"].values()
            for o in ops if o.get("options", {}).get("id") == OP]
    if len({repr(o) for o in opts}) != 1:
        return None
    prompt, cand = len(opts[0]["prompt"]), opts[0]["candidates"]
    side = spec["patch_size"] * spec["patch_merge"]
    h, w, _ = r.image_shape
    image_tokens = -(-h // side) * -(-w // side)
    f_pre, f_dec = entity_flops(spec, image_tokens, prompt, len(cand),
                                len(cand[0]))
    S = image_tokens + prompt
    steps = len(cand) * (len(cand[0]) - 1)
    flops = pre * f_pre / S + dec * f_dec / steps
    peak = r.peaks["flops_per_s"] * r.trace["window_s"] * r.trace["devices"]
    return 100.0 * flops / peak
