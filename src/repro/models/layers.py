"""Shared neural-net building blocks (pure functional JAX).

Params are plain dict pytrees. Every module is an (init, apply) pair.
Compute convention: activations in cfg.dtype (bf16), normalization and
softmax statistics in fp32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


# ------------------------------------------------------------ sharding hints

def current_mesh():
    """The (abstract) mesh set by an enclosing `jax.set_mesh(mesh)`, or
    None. Readable inside jit traces and shard_map bodies."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def mesh_axis(name: str) -> int:
    m = current_mesh()
    return m.shape[name] if (m is not None and name in m.axis_names) else 1


def dp_spec():
    """The data-parallel axes of the active mesh (pod folds into DP)."""
    m = current_mesh()
    if m is None:
        return None
    axes = tuple(a for a in ("pod", "data") if a in m.axis_names)
    return axes if axes else None


def shard_hint(x, *spec):
    """with_sharding_constraint against the ACTIVE mesh; silently a no-op
    outside a mesh context (single-device tests / examples). Axis entries
    whose name is absent from the mesh are dropped to None."""
    m = current_mesh()
    if m is None:
        return x
    from jax.sharding import PartitionSpec
    names = set(m.axis_names)

    def clean(e):
        if e is None:
            return None
        if isinstance(e, tuple):
            return e if (e and all(a in names for a in e)) else None
        return e if e in names else None

    return jax.lax.with_sharding_constraint(
        x, PartitionSpec(*[clean(e) for e in spec]))


# ---------------------------------------------------------------- init utils

def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = 1.0 / np.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


def split(key, n: int):
    return list(jax.random.split(key, n))


# ------------------------------------------------------------------- RMSNorm

def rmsnorm_init(d: int) -> dict:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------- RoPE

def rope_angles(positions: jax.Array, head_dim: int, theta: float) -> tuple:
    """positions [*, S] -> (cos, sin) each [*, S, head_dim//2], fp32."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [..., S, H, D]; cos/sin broadcastable [..., S, 1, D//2]."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- SwiGLU

def mlp_init(key, d: int, d_ff: int, dtype) -> dict:
    k1, k2, k3 = split(key, 3)
    return {
        "wi": dense_init(k1, d, d_ff, dtype),
        "wg": dense_init(k2, d, d_ff, dtype),
        "wo": dense_init(k3, d_ff, d, dtype),
    }


def mlp(params: dict, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ params["wg"]) * (x @ params["wi"])
    return h @ params["wo"]


def gelu_mlp_init(key, d: int, d_ff: int, dtype) -> dict:
    k1, k2 = split(key, 2)
    return {"wi": dense_init(k1, d, d_ff, dtype), "wo": dense_init(k2, d_ff, d, dtype)}


def gelu_mlp(params: dict, x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x @ params["wi"]) @ params["wo"]


# ------------------------------------------------------------------- softcap

def softcap(x: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


# -------------------------------------------------------- stacked-layer init

def stack_init(key, n: int, init_fn):
    """vmap an init over n layer keys -> stacked param pytree [n, ...]."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)
