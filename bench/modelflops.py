"""The model's useful operations per engine tick, from the configuration's
own sizes: 2 x the active weights for each token prefilled or generated,
plus causal attention (4 x heads x head_dim per key attended, for Q.K and
P.V). A prefill computes the output head at its last position only, so a
prefilled token counts the head only there. Padding, and work a kernel does
beyond the algorithm's (masked key-value heads, dead tiles), counts for
nothing."""
from __future__ import annotations


def body_params(sz: dict) -> int:
    """Weights one token multiplies by below the output head."""
    d, hd = sz["d_model"], sz["head_dim"]
    attn = d * hd * (2 * sz["heads"] + 2 * sz["kv_heads"])
    moe = (sz["top_k"] + sz["shared_experts"]) * 3 * d * sz["d_expert"]
    return sz["layers"] * (attn + moe + d * sz["experts"])


def attn_flops(sz: dict, keys: int) -> int:
    """Causal attention of one query over `keys` keys, all layers."""
    return 4 * sz["heads"] * sz["head_dim"] * keys * sz["layers"]


def prefill_flops(sz: dict, start: int, n: int, last: bool) -> float:
    """n prompt tokens at positions start..start+n-1."""
    keys = n * start + n * (n + 1) // 2
    head = 2 * sz["d_model"] * sz["vocab"] if last else 0
    return 2 * body_params(sz) * n + attn_flops(sz, keys) + head


def step_flops(sz: dict, step) -> float:
    head = 2 * sz["d_model"] * sz["vocab"]
    f = sum(2 * body_params(sz) + head + attn_flops(sz, t + 1)
            for t in step.decode)
    f += sum(prefill_flops(sz, 0, n, True) for n in step.oneshot)
    f += sum(prefill_flops(sz, s, v, last) for s, v, last in step.chunks)
    return f
