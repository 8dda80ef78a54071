"""Plain reference of a DeepSeek-V3-block decoder: multi-head latent
attention (MLA) with no query LoRA, leading dense SwiGLU layers, then MoE
layers with a sigmoid, bias-selected, scaled router. It follows the
configuration file (Hugging Face keys, as run) and the published
`DeepseekV3Attention` / `DeepseekV3MoE` equations, and imports nothing of
the program under test.

x = embedding; per layer: x += MLA(RMSNorm(x)); x += FFN(RMSNorm(x)).
  MLA   q = x W_q, per head [qk_nope | qk_rope]; kv = x W_kva, split
        [kv_lora_rank | qk_rope]; c = RMSNorm(kv[:rank]); k_rope =
        rope(kv[rank:]), one key shared by every head; per head
        [k_nope | v] = c W_kvb; scores (q_nope . k_nope + q_rope . k_rope)
        / sqrt(qk_nope + qk_rope) (no rope scaling), causal softmax, then
        the heads' outputs through W_o. Rotary embedding as the source
        applies it: the interleaved pairs (2i, 2i+1) of each rotary part
        rotate by position * theta^(-2i/rope) (the source de-interleaves
        them to a rotate-half layout first).
  FFN   the first `first_k_dense_replace` layers: SwiGLU of width
        intermediate_size. The others: router logits x W_gate (f32);
        scores sigmoid(logits); the top-k experts of scores + bias
        (e_score_correction_bias; one expert group, so group-limited
        selection chooses from all experts); their weights the unbiased
        scores, renormalised to sum to 1 (norm_topk_prob), times
        routed_scaling_factor; each expert a SwiGLU FFN (silu(x Wg) *
        (x Wi)) Wo; shared experts added unweighted.
Final RMSNorm, then logits against the output head.

Departures from the source, each forced by the harness or the program:
  - the output head is tied to the embedding (`assumed`:
    tie_word_embeddings true; bench/serve.py::check_program_matches runs
    every configuration tied);
  - weights come in the program's layout (`make_weights`): the rotary
    columns of W_q and W_kva are stored permuted from the published
    interleaved order to the program's rotate-half order, and the
    reference undoes that permutation before its interleaved rotation, so
    it computes in the published layout;
  - attention runs in blocks of queries, so that 8k-token sequences fit.

`gaps` runs this over each prompt with its served tokens in float32 at the
`highest` matmul precision, layer by layer, and returns how far each served
token's logit lies below the reference's best at its position; the output
head is computed at those positions only. `control=True` reads the
control: the same forward in float8_e4m3fn, the precision below the
configuration's bfloat16 (every product's operands rounded to it under one
scale per tensor, as in references/moe_decoder.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.references.moe_decoder import (_att, _ffn, _gap_of, _mm, _rms,
                                          _routed, _shared, as_run, bucket,
                                          seed_key)

Q_BLOCK = 512           # query rows of one attention block


def sizes(conf: dict) -> dict:
    """Canonical sizes and settings from a configuration file's Hugging
    Face keys, as run, with the latent-attention and router keys."""
    conf = as_run(conf)
    if conf.get("q_lora_rank") or conf.get("rope_scaling"):
        raise ValueError("the reference has no query LoRA and no rope "
                         "scaling")
    if conf.get("n_group", 1) != 1 or conf.get("topk_group", 1) != 1:
        raise ValueError("the reference routes over one expert group")
    if conf.get("scoring_func") != "sigmoid" or \
            not conf.get("tie_word_embeddings", True):
        raise ValueError("the reference holds a sigmoid router and a tied "
                         "head")
    rope, nope = conf["qk_rope_head_dim"], conf["qk_nope_head_dim"]
    hd = rope + nope
    return {
        "layers": conf["num_hidden_layers"],
        "dense_layers": conf["first_k_dense_replace"],
        "d_model": conf["hidden_size"],
        "d_ff": conf["intermediate_size"],
        "heads": conf["num_attention_heads"],
        "kv_heads": conf["num_key_value_heads"],
        "head_dim": hd,
        "qk_nope": nope,
        "qk_rope": rope,
        "v_head": conf["v_head_dim"],
        "kv_lora": conf["kv_lora_rank"],
        "vocab": conf["vocab_size"],
        "experts": conf["n_routed_experts"],
        "top_k": conf["num_experts_per_tok"],
        "d_expert": conf["moe_intermediate_size"],
        "shared_experts": conf["n_shared_experts"],
        "routed_scale": float(conf["routed_scaling_factor"]),
        "rope_theta": float(conf["rope_theta"]),
        "norm_eps": float(conf["rms_norm_eps"]),
        "dtype": conf["torch_dtype"],
        "emb_mult": 1.0,
        "res_mult": 1.0,
        "attn_scale": float(hd ** -0.5),
        "logit_scale": 1.0,
        "norm_topk": bool(conf["norm_topk_prob"]),
    }


# --------------------------------------------------------------- weights

def rope_permutation(rope: int) -> np.ndarray:
    """Program rotary column j holds published column perm[j]: the even
    members of the interleaved pairs, then the odd."""
    return np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])


def make_weights(sz: dict, seed: int, shardings=None) -> dict:
    """Random weights in the program's parameter layout and served dtype:
    matrices N(0, 1/fan_in), the embedding N(0, 0.02^2), norm scales
    1 + N(0, 0.1^2), router gate and bias in float32 (bias N(0, 0.1^2):
    large enough to change which experts are chosen). Drawn in the
    published layout, then the rotary columns of W_q and W_kva permuted to
    the program's (`rope_permutation`). `shardings` as in
    references/moe_decoder.py."""
    frozen = tuple(sorted(sz.items()))
    if shardings is None:
        return _init_jit(seed_key(seed), frozen)
    return jax.jit(_init, static_argnums=1, out_shardings=shardings)(
        seed_key(seed), frozen)


def _init(key, frozen):
    sz = dict(frozen)
    dt = jnp.dtype(sz["dtype"])
    d, V, H = sz["d_model"], sz["vocab"], sz["heads"]
    nope, rope, vd, r = sz["qk_nope"], sz["qk_rope"], sz["v_head"], \
        sz["kv_lora"]
    E, f, S = sz["experts"], sz["d_expert"], sz["shared_experts"]
    perm = rope_permutation(rope)

    def normal(k, shape, scale, dtype=dt):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    def norm(k, n=d):
        return {"scale": 1.0 + normal(k, (n,), 0.1, jnp.float32)}

    def bank(k, n, width):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"wi": normal(k1, (n, d, width), d ** -0.5),
                "wg": normal(k2, (n, d, width), d ** -0.5),
                "wo": normal(k3, (n, width, d), width ** -0.5)}

    def attn(k):
        ks = jax.random.split(k, 5)
        wq = normal(ks[0], (d, H, nope + rope), d ** -0.5)
        wq = wq.at[..., nope:].set(wq[..., nope + perm])
        wkva = normal(ks[1], (d, r + rope), d ** -0.5)
        wkva = wkva.at[:, r:].set(wkva[:, r + perm])
        return {"wq": wq.reshape(d, H * (nope + rope)), "wkva": wkva,
                "kv_norm": norm(ks[2], r),
                "wkv_b": normal(ks[3], (r, H, nope + vd), r ** -0.5),
                "wo": normal(ks[4], (H * vd, d), (H * vd) ** -0.5)}

    def dense_layer(k):
        ks = jax.random.split(k, 4)
        mlp = {kk: v[0] for kk, v in bank(ks[1], 1, sz["d_ff"]).items()}
        return {"ln1": norm(ks[0]), "attn": attn(ks[2]), "ln2": norm(ks[3]),
                "mlp": mlp}

    def moe_layer(k):
        ks = jax.random.split(k, 7)
        moe = {"gate": normal(ks[0], (d, E), d ** -0.5, jnp.float32),
               "bias": normal(ks[1], (E,), 0.1, jnp.float32),
               "experts": bank(ks[2], E, f)}
        if S:
            moe["shared"] = bank(ks[3], S, f)
        return {"ln1": norm(ks[4]), "attn": attn(ks[5]), "ln2": norm(ks[6]),
                "moe": moe}

    k_dense, k_layers, k_embed, k_norm = jax.random.split(key, 4)
    nd = sz["dense_layers"]
    out = {"embed": normal(k_embed, (V, d), 0.02),
           "final_norm": norm(k_norm),
           "layers": jax.lax.map(moe_layer, jax.random.split(
               k_layers, sz["layers"] - nd))}
    if nd:
        out["dense_layers"] = jax.lax.map(dense_layer,
                                          jax.random.split(k_dense, nd))
    return out


_init_jit = jax.jit(_init, static_argnums=1)


# ------------------------------------------------------------- reference

def _rope_interleaved(x, pos, theta):
    """x [S, ..., D] in the published layout: rotate each interleaved pair
    (x[2i], x[2i+1]) by pos * theta^(-2i/D), output in the rotate-half
    layout the source leaves it in (only dot products read it)."""
    D = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos.astype(jnp.float32).reshape(-1, *([1] * (x.ndim - 1))) * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    xe, xo = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([xe * c - xo * s, xo * c + xe * s], -1)


def _attend(x, lp, sz, fp8):
    """x plus the layer's latent attention, in blocks of Q_BLOCK queries."""
    S = x.shape[0]
    H, nope, rope, vd, r = (sz["heads"], sz["qk_nope"], sz["qk_rope"],
                            sz["v_head"], sz["kv_lora"])
    a = lp["attn"]
    inv = np.argsort(rope_permutation(rope))      # program -> published
    pos = jnp.arange(S)
    h = _rms(x, lp["ln1"]["scale"], sz["norm_eps"])
    q = _mm(h, a["wq"], fp8).reshape(S, H, nope + rope)
    kv = _mm(h, a["wkva"], fp8)
    c = _rms(kv[:, :r], a["kv_norm"]["scale"], sz["norm_eps"])
    k_rope = _rope_interleaved(kv[:, r:][:, inv], pos, sz["rope_theta"])
    q = jnp.concatenate(
        [q[..., :nope],
         _rope_interleaved(q[..., nope:][..., inv], pos, sz["rope_theta"])],
        -1)
    kvb = _mm(c, a["wkv_b"].reshape(r, H * (nope + vd)), fp8).reshape(
        S, H, nope + vd)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_rope[:, None], (S, H, rope))],
        -1)
    v = kvb[..., nope:]
    n = -(-S // Q_BLOCK)
    qb = jnp.pad(q, ((0, n * Q_BLOCK - S), (0, 0), (0, 0))).reshape(
        n, Q_BLOCK, H, nope + rope)

    def block(args):
        i, qi = args
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = _att("shd,thd->hst", qi, k, fp8) * sz["attn_scale"]
        s = jnp.where(qpos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        return _att("hst,thd->shd", jax.nn.softmax(s, -1), v, fp8)

    o = jax.lax.map(block, (jnp.arange(n), qb)).reshape(n * Q_BLOCK, H, vd)
    return x + _mm(o[:S].reshape(S, H * vd), a["wo"], fp8)


def _route(h, m, sz, fp8):
    """Each token's weight per expert, [S, E]: zero outside its top k."""
    s = jax.nn.sigmoid(_mm(h, m["gate"], fp8))
    _, idx = jax.lax.top_k(s + m["bias"], sz["top_k"])
    top = jnp.take_along_axis(s, idx, -1)
    if sz["norm_topk"]:
        top = top / top.sum(-1, keepdims=True)
    top = top * sz["routed_scale"]
    rows = jnp.arange(h.shape[0])
    return jnp.zeros_like(s).at[rows[:, None], idx].set(top)


@functools.partial(jax.jit, static_argnames=("frozen", "fp8"))
def _dense_layer(x, lp, *, frozen, fp8):
    sz = dict(frozen)
    x = _attend(x, lp, sz, fp8)
    h = _rms(x, lp["ln2"]["scale"], sz["norm_eps"])
    w = lp["mlp"]
    return x + _ffn(h, w["wg"], w["wi"], w["wo"], fp8)


@functools.partial(jax.jit, static_argnames=("frozen", "fp8"))
def _moe_layer(x, lp, *, frozen, fp8):
    sz = dict(frozen)
    x = _attend(x, lp, sz, fp8)
    h = _rms(x, lp["ln2"]["scale"], sz["norm_eps"])
    comb = _route(h, lp["moe"], sz, fp8)
    y = _routed(h, comb, lp["moe"]["experts"], sz["experts"], fp8)
    return x + _shared(h, lp["moe"], sz, fp8, y)


@functools.partial(jax.jit, static_argnames=("frozen", "fp8"))
def _head(x, final_scale, embed, *, frozen, fp8):
    sz = dict(frozen)
    h = _rms(x, final_scale, sz["norm_eps"])
    return _mm(h, embed.T, fp8)


def hidden(weights: dict, sz: dict, tokens: np.ndarray, *,
           fp8: bool = False) -> jax.Array:
    """The last layer's output [S, d] (f32) of a token sequence, layer by
    layer."""
    frozen = tuple(sorted(sz.items()))
    x = weights["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for name, layer in (("dense_layers", _dense_layer),
                        ("layers", _moe_layer)):
        stack = weights.get(name)
        n = 0 if stack is None else jax.tree.leaves(stack)[0].shape[0]
        for i in range(n):
            lp = jax.tree.map(lambda a: a[i], stack)
            x = layer(x, lp, frozen=frozen, fp8=fp8)
    return x


def logits(weights: dict, sz: dict, tokens: np.ndarray, *,
           fp8: bool = False, start: int = 0, n: int | None = None
           ) -> jax.Array:
    """Logits [n, V] (f32) at positions start..start+n-1 of a sequence
    (all of it by default). The head runs over a power-of-two bucket of
    rows, so one compiled head serves a run's sequences."""
    x = hidden(weights, sz, tokens, fp8=fp8)
    S = x.shape[0]
    n = S - start if n is None else n
    nb = min(bucket(n), S)
    lo = min(start, S - nb)
    out = _head(jax.lax.dynamic_slice_in_dim(x, lo, nb),
                weights["final_norm"]["scale"], weights["embed"],
                frozen=tuple(sorted(sz.items())), fp8=fp8)
    return out[start - lo:start - lo + n]


def gaps(weights: dict, sz: dict, prompt: np.ndarray, served: np.ndarray,
         *, length: int, control: bool = False) -> np.ndarray:
    """For each served token j (greedy, emitted after prompt + served[:j]):
    the reference's best logit minus its logit of served[j], the sequence
    padded to `length`. With `control` the tokens judged are those the fp8
    forward puts first instead."""
    p, n = len(prompt), len(served)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    seq = np.pad(seq, (0, length - len(seq)))      # causal: pads see nothing
    ref = logits(weights, sz, seq, start=p - 1, n=n)
    if control:
        judged = jnp.argmax(logits(weights, sz, seq, fp8=True, start=p - 1,
                                   n=n), -1)
    else:
        judged = jnp.asarray(served, jnp.int32)
    return np.asarray(_gap_of(ref, judged))
