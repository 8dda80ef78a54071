"""Plain reference of a decoder of attention + token-choice MoE blocks, the
family both configurations of this benchmark belong to. It follows the
configuration file (Hugging Face keys, as run) and imports nothing of the
program under test.

x = embedding * embedding_multiplier; per block:
x += residual_multiplier * Attn(RMSNorm(x)); x += residual_multiplier *
MoE(RMSNorm(x)).
  Attn  grouped-query attention, rotary embedding on the two halves of each
        head (GPT-NeoX layout), causal softmax of the scores times
        attention_multiplier (default 1/sqrt(head_dim)), no biases.
  MoE   router logits x @ W_gate; the top-k experts by logit; their weights
        are the softmax over all experts' logits, kept at the top k and,
        with `norm_topk_prob`, renormalised to sum to 1; each expert is a
        SwiGLU FFN (silu(x Wg) * (x Wi)) Wo; shared experts are added
        unweighted.
Final RMSNorm, then logits against the tied embedding, over logits_scaling.
The model is run as the file states it, with the values of its `assumed`
object (what the program under test forces) over the published ones.

`gaps` runs this over each prompt with its served tokens, in float32 at
the `highest` matmul precision, layer by layer, and returns how far each
served token's logit lies below the reference's best at its position.
`control=True` reads the control: the same forward computed in
float8_e4m3fn, the precision below the configuration's bfloat16: every
product's operands (weights, activations, router, attention scores,
probabilities and values) rounded to it under one scale per tensor.

Also here: `sizes` (the canonical sizes read from the configuration file)
and `make_weights` (random weights from the seed, in the program's
parameter layout and served dtype, made in one jitted call on the device).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

HIGHEST = jax.lax.Precision.HIGHEST
WEIGHT_STREAM = 2       # the seed's stream for weights (traffic uses 1)
FP8_MAX = 448.0         # largest finite float8_e4m3fn


def as_run(conf: dict) -> dict:
    """The configuration as it is run: the published keys with the file's
    `assumed` values over them."""
    return {**conf, **conf.get("assumed", {})}


def sizes(conf: dict) -> dict:
    """Canonical sizes and settings from a configuration file's Hugging
    Face keys, as run."""
    conf = as_run(conf)
    if conf.get("first_k_dense_replace", 0) or \
            not conf.get("tie_word_embeddings", True):
        raise ValueError("the reference holds MoE blocks only and a tied "
                         "head")
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    hd = conf.get("head_dim", d // h)
    return {
        "layers": conf["num_hidden_layers"],
        "d_model": d,
        "heads": h,
        "kv_heads": conf["num_key_value_heads"],
        "head_dim": hd,
        "vocab": conf["vocab_size"],
        "experts": conf.get("num_local_experts", conf.get("n_routed_experts")),
        "top_k": conf["num_experts_per_tok"],
        "d_expert": conf.get("moe_intermediate_size",
                             conf["intermediate_size"]),
        "shared_experts": conf.get("n_shared_experts", 0) or 0,
        "rope_theta": float(conf["rope_theta"]),
        "norm_eps": float(conf["rms_norm_eps"]),
        "dtype": conf["torch_dtype"],
        "emb_mult": float(conf.get("embedding_multiplier", 1.0)),
        "res_mult": float(conf.get("residual_multiplier", 1.0)),
        "attn_scale": float(conf.get("attention_multiplier", hd ** -0.5)),
        "logit_scale": float(conf.get("logits_scaling", 1.0)),
        "norm_topk": bool(conf.get("norm_topk_prob", True)),
    }


# --------------------------------------------------------------- weights

def seed_key(seed: int, stream: int = WEIGHT_STREAM):
    s = int(seed) % (1 << 64)
    key = jax.random.fold_in(jax.random.PRNGKey(stream), s & 0xFFFFFFFF)
    return jax.random.fold_in(key, s >> 32)


def make_weights(sz: dict, seed: int, shardings=None) -> dict:
    """Random weights in the served dtype: matrices N(0, 1/fan_in), the
    embedding N(0, 0.02^2), norm scales 1 + N(0, 0.1^2), router in float32
    (the program keeps its router in float32). `shardings`, a tree of
    shardings in the weights' layout, has the same call make each leaf
    already laid out so over its devices: the same key gives the same bits
    (JAX's partitionable random bits), and no leaf is ever whole on one
    device."""
    frozen = tuple(sorted(sz.items()))
    if shardings is None:
        return _init_jit(seed_key(seed), frozen)
    return jax.jit(_init, static_argnums=1, out_shardings=shardings)(
        seed_key(seed), frozen)


def _init(key, frozen):
    sz = dict(frozen)
    dt = jnp.dtype(sz["dtype"])
    L, d, V = sz["layers"], sz["d_model"], sz["vocab"]
    hd, hq, hkv = sz["head_dim"], sz["heads"], sz["kv_heads"]
    E, f, S = sz["experts"], sz["d_expert"], sz["shared_experts"]

    def normal(k, shape, scale, dtype=dt):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    def norm(k):
        return {"scale": 1.0 + normal(k, (d,), 0.1, jnp.float32)}

    def bank(k, n):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"wi": normal(k1, (n, d, f), d ** -0.5),
                "wg": normal(k2, (n, d, f), d ** -0.5),
                "wo": normal(k3, (n, f, d), f ** -0.5)}

    def layer(k):
        ks = jax.random.split(k, 9)
        moe = {"gate": normal(ks[0], (d, E), d ** -0.5, jnp.float32),
               "experts": bank(ks[1], E)}
        if S:
            moe["shared"] = bank(ks[2], S)
        return {"ln1": norm(ks[3]),
                "attn": {"wq": normal(ks[4], (d, hq * hd), d ** -0.5),
                         "wk": normal(ks[5], (d, hkv * hd), d ** -0.5),
                         "wv": normal(ks[6], (d, hkv * hd), d ** -0.5),
                         "wo": normal(ks[7], (hq * hd, d), (hq * hd) ** -0.5)},
                "ln2": norm(ks[8]),
                "moe": moe}

    k_layers, k_embed, k_norm = jax.random.split(key, 3)
    return {"embed": normal(k_embed, (V, d), 0.02),
            "final_norm": norm(k_norm),
            "layers": jax.lax.map(layer, jax.random.split(k_layers, L))}


_init_jit = jax.jit(_init, static_argnums=1)


# ------------------------------------------------------------- reference

def _qdq(a):
    """Round to float8_e4m3fn under one per-tensor scale, back to f32."""
    a = a.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, fp8: bool):
    """A weight matmul: f32 at the highest precision, or the fp8 control."""
    a, w = a.astype(jnp.float32), w.astype(jnp.float32)
    if fp8:
        a, w = _qdq(a), _qdq(w)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x [S, H, D]; rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _ffn(x, wg, wi, wo, fp8):
    return _mm(jax.nn.silu(_mm(x, wg, fp8)) * _mm(x, wi, fp8), wo, fp8)


def _att(spec, a, b, fp8):
    """An attention product (scores or probabilities times values)."""
    if fp8:
        a, b = _qdq(a), _qdq(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _attend(x, lp, sz, fp8):
    """x plus the block's attention."""
    S = x.shape[0]
    hd, hq, hkv = sz["head_dim"], sz["heads"], sz["kv_heads"]
    pos = jnp.arange(S)
    a = lp["attn"]
    h = _rms(x, lp["ln1"]["scale"], sz["norm_eps"])
    q = _rope(_mm(h, a["wq"], fp8).reshape(S, hq, hd), pos, sz["rope_theta"])
    kk = _rope(_mm(h, a["wk"], fp8).reshape(S, hkv, hd), pos,
               sz["rope_theta"])
    v = _mm(h, a["wv"], fp8).reshape(S, hkv, hd)
    kk = jnp.repeat(kk, hq // hkv, axis=1)          # q head i -> kv head i // G
    v = jnp.repeat(v, hq // hkv, axis=1)
    s = _att("shd,thd->hst", q, kk, fp8) * sz["attn_scale"]
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = _att("hst,thd->shd", jax.nn.softmax(s, -1), v, fp8)
    return x + sz["res_mult"] * _mm(o.reshape(S, hq * hd), a["wo"], fp8)


def _route(x, lp, sz, fp8):
    """The MoE input and each token's weight per expert, [S, E]: zero
    outside its top k."""
    h = _rms(x, lp["ln2"]["scale"], sz["norm_eps"])
    logits = _mm(h, lp["moe"]["gate"], fp8)
    top, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), sz["top_k"])
    if sz["norm_topk"]:
        top = top / top.sum(-1, keepdims=True)
    pos = jnp.arange(x.shape[0])
    return h, jnp.zeros_like(logits).at[pos[:, None], idx].set(top)


def _routed(h, comb, ex, n, fp8, first=None):
    """The routed experts' weighted sum, expert by expert in order, over the
    n experts of the bank `ex`; they are experts first.. of the router's
    (`first` None: the bank holds them all)."""
    def expert(e, y):
        col = e if first is None else first + e
        return y + comb[:, col, None] * _ffn(h, ex["wg"][e], ex["wi"][e],
                                             ex["wo"][e], fp8)

    return jax.lax.fori_loop(0, n, expert, jnp.zeros_like(h))


def _shared(h, m, sz, fp8, y):
    """y plus the shared experts, unweighted."""
    if "shared" in m:
        sh = m["shared"]
        y = jax.lax.fori_loop(
            0, sz["shared_experts"],
            lambda e, y: y + _ffn(h, sh["wg"][e], sh["wi"][e], sh["wo"][e],
                                  fp8), y)
    return y


@functools.partial(jax.jit, static_argnames=("frozen", "fp8"))
def _layer(x, lp, *, frozen, fp8):
    sz = dict(frozen)
    x = _attend(x, lp, sz, fp8)
    h, comb = _route(x, lp, sz, fp8)
    y = _routed(h, comb, lp["moe"]["experts"], sz["experts"], fp8)
    return x + sz["res_mult"] * _shared(h, lp["moe"], sz, fp8, y)


@functools.partial(jax.jit, static_argnames=("frozen", "fp8", "mesh", "axis"))
def _layer_split(x, lp, *, frozen, fp8, mesh, axis):
    """`_layer` with the routed experts split over the mesh axis `axis`, as
    the weights hold them: each device sums its own experts over every
    token, the partial sums are added across devices, and everything else
    is computed whole on each device. The same sums as `_layer`, added in
    another order."""
    sz = dict(frozen)
    n = sz["experts"] // mesh.shape[axis]

    def body(x, lp):
        x = _attend(x, lp, sz, fp8)
        h, comb = _route(x, lp, sz, fp8)
        y = _routed(h, comb, lp["moe"]["experts"], n, fp8,
                    first=jax.lax.axis_index(axis) * n)
        y = jax.lax.psum(y, axis)
        return x + sz["res_mult"] * _shared(h, lp["moe"], sz, fp8, y)

    specs = jax.tree.map(lambda _: P(), lp)
    specs["moe"]["experts"] = jax.tree.map(lambda _: P(axis),
                                           lp["moe"]["experts"])
    return jax.shard_map(body, mesh=mesh, in_specs=(P(), specs),
                         out_specs=P(), check_vma=False)(x, lp)


def _expert_split(weights: dict):
    """(mesh, axis) where the weights hold the routed experts split over a
    mesh axis, else None."""
    sh = getattr(weights["layers"]["moe"]["experts"]["wg"], "sharding", None)
    spec = getattr(sh, "spec", ())
    if len(spec) > 1 and isinstance(spec[1], str) and \
            sh.mesh.shape[spec[1]] > 1:
        return sh.mesh, spec[1]
    return None


@functools.partial(jax.jit, static_argnames=("frozen", "fp8"))
def _head(x, final_scale, embed, *, frozen, fp8):
    sz = dict(frozen)
    h = _rms(x, final_scale, sz["norm_eps"])
    return _mm(h, embed.T, fp8) / sz["logit_scale"]


@jax.jit
def _gap_of(ref, tokens):
    """How far `tokens` [S] sit below the best of `ref` [S, V] per row."""
    return ref.max(-1) - jnp.take_along_axis(ref, tokens[:, None], -1)[:, 0]


def logits(weights: dict, sz: dict, tokens: np.ndarray, *,
           fp8: bool = False) -> jax.Array:
    """Logits [S, V] (f32) of a token sequence, layer by layer; where the
    weights hold the routed experts split over a mesh, each layer splits its
    expert sum alike (`_layer_split`)."""
    frozen = tuple(sorted(sz.items()))
    x = weights["embed"][jnp.asarray(tokens)].astype(jnp.float32) * \
        sz["emb_mult"]
    layers = weights["layers"]
    split = _expert_split(weights)
    for i in range(sz["layers"]):
        lp = jax.tree.map(lambda a: a[i], layers)
        if split is None:
            x = _layer(x, lp, frozen=frozen, fp8=fp8)
        else:
            x = _layer_split(x, lp, frozen=frozen, fp8=fp8, mesh=split[0],
                             axis=split[1])
    return _head(x, weights["final_norm"]["scale"], weights["embed"],
                 frozen=frozen, fp8=fp8)


def bucket(n: int) -> int:
    b = 256
    while b < n:
        b *= 2
    return b


def gaps(weights: dict, sz: dict, prompt: np.ndarray, served: np.ndarray,
         *, length: int, control: bool = False) -> np.ndarray:
    """For each served token j (greedy, emitted after prompt + served[:j]):
    the reference's best logit minus its logit of served[j], the sequence
    padded to `length` (one compiled shape for every sequence of a run).
    With `control` the tokens judged are those the fp8 forward puts first
    instead."""
    p, n = len(prompt), len(served)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    seq = np.pad(seq, (0, length - len(seq)))      # causal: pads see nothing
    rows = slice(p - 1, p - 1 + n)
    ref = logits(weights, sz, seq)[rows]
    if control:
        judged = jnp.argmax(logits(weights, sz, seq, fp8=True)[rows], -1)
    else:
        judged = jnp.asarray(served, jnp.int32)
    return np.asarray(_gap_of(ref, judged))
