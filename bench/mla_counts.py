"""Operation and byte counts of a latent-attention (DeepSeek-V3 block)
configuration, from its reference sizes (bench/references/
mla_moe_decoder.py `sizes`).

The model's useful operations are the published forward's, in its
non-absorbed form: per token and layer 2 x the weights it multiplies by
(the query, latent-and-rotary-key, key-and-value up-projection and output
matrices; the dense layers' SwiGLU, or the router and the top-k routed and
shared experts of an MoE layer), plus causal attention, (2 x head_dim +
2 x v_head_dim) x heads per key attended (Q.K and P.V). The output head
counts at each generated token and at a prefill's last position. So the
form the program computes (absorbed decode) moves the time and not this
yardstick. Padding and work beyond the algorithm count for nothing.

The latent-attention decode kernel's least time: for a decode row at
position t, per layer, 2 x heads x (rank + rope) x (t + 1) operations to
score the cached latent rows and 2 x heads x rank x (t + 1) to weight
them; bytes its live pages of rows (t // page + 1 of them, page x
(rank + rope) values in bf16, the stored row's zero tail not counted),
the query (bf16) and the output (f32). The least time is the larger of
operations / peak and bytes / bandwidth.
"""
from __future__ import annotations


def attn_params(sz: dict) -> int:
    """Weights of one latent-attention layer a token multiplies by."""
    d, H, r = sz["d_model"], sz["heads"], sz["kv_lora"]
    nope, rope, vd = sz["qk_nope"], sz["qk_rope"], sz["v_head"]
    return (d * H * (nope + rope) + d * (r + rope) + r * H * (nope + vd)
            + H * vd * d)


def body_params(sz: dict) -> int:
    """Weights one token multiplies by below the output head, all
    layers."""
    d = sz["d_model"]
    nd = sz["dense_layers"]
    moe = ((sz["top_k"] + sz["shared_experts"]) * 3 * d * sz["d_expert"]
           + d * sz["experts"])
    return (sz["layers"] * attn_params(sz) + nd * 3 * d * sz["d_ff"]
            + (sz["layers"] - nd) * moe)


def attn_flops(sz: dict, keys: int) -> int:
    """Causal attention of one query over `keys` keys, all layers."""
    per_key = 2 * sz["head_dim"] + 2 * sz["v_head"]
    return per_key * sz["heads"] * keys * sz["layers"]


def prefill_flops(sz: dict, start: int, n: int, last: bool) -> float:
    """n prompt tokens at positions start..start+n-1."""
    keys = n * start + n * (n + 1) // 2
    head = 2 * sz["d_model"] * sz["vocab"] if last else 0
    return 2 * body_params(sz) * n + attn_flops(sz, keys) + head


def step_flops(sz: dict, step) -> float:
    """The model's useful operations in one engine tick (bench/serve.py
    `Step`): its decode rows, one-shot prefills and chunks."""
    head = 2 * sz["d_model"] * sz["vocab"]
    f = sum(2 * body_params(sz) + head + attn_flops(sz, t + 1)
            for t in step.decode)
    f += sum(prefill_flops(sz, 0, n, True) for n in step.oneshot)
    f += sum(prefill_flops(sz, s, v, last) for s, v, last in step.chunks)
    return f


def decode_kernel_least_s(sz: dict, ps: int, positions, peaks: dict) -> float:
    """Least time of the latent-attention decode kernel over one tick's
    decode rows at `positions`, all layers."""
    H, r = sz["heads"], sz["kv_lora"]
    w = r + sz["qk_rope"]
    flops = sum(2 * H * w * (t + 1) + 2 * H * r * (t + 1) for t in positions)
    byts = sum((t // ps + 1) * ps * w * 2 + H * w * 2 + H * r * 4
               for t in positions)
    return sz["layers"] * max(flops / peaks["flops_bf16"],
                              byts / peaks["hbm_bytes_per_s"])
