"""Foundational layers: norms, RoPE, MLPs, embeddings, logits.

All layers are pure functions over parameter dicts (pytree leaves are
jnp arrays; stacked along a leading L axis when scanned over layers).
Initializers return the same tree structure, so ``jax.eval_shape`` yields
allocation-free ShapeDtypeStruct trees for the dry-run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["rms_norm", "rope", "swiglu", "init_linear", "init_rmsnorm",
           "linear", "embed", "unembed", "init_embed", "truncated_normal",
           "maybe_constrain"]


def maybe_constrain(x: jnp.ndarray, *axes) -> jnp.ndarray:
    """with_sharding_constraint that no-ops when the named axes are absent
    (CPU smoke tests run mesh-less; the dry-run/train run under set_mesh)."""
    from jax.sharding import PartitionSpec as P
    mesh_axes = set(jax.sharding.get_abstract_mesh().axis_names)
    spec = tuple(a if (a in mesh_axes) else None for a in axes)
    if not any(spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def truncated_normal(key, shape, scale: float, dtype) -> jnp.ndarray:
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


# ------------------------------------------------------------------- norms
def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rms_norm(p: dict, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


# -------------------------------------------------------------------- RoPE
def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 1e4) -> jnp.ndarray:
    """x: (..., L, D) with D even; positions: (..., L) int."""
    D = x.shape[-1]
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs   # (..., L, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- linears
def init_linear(key, d_in: int, d_out, dtype, bias: bool = False,
                scale: float | None = None) -> dict:
    shape = (d_in,) + (tuple(d_out) if isinstance(d_out, (tuple, list)) else (d_out,))
    p = {"w": truncated_normal(key, shape, scale or (d_in ** -0.5), dtype)}
    if bias:
        p["b"] = jnp.zeros(shape[1:], dtype)
    return p


def linear(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    w = p["w"]
    y = jax.lax.dot_general(x, w.reshape(w.shape[0], -1),
                            (((x.ndim - 1,), (0,)), ((), ())))
    y = y.reshape(x.shape[:-1] + w.shape[1:])
    if "b" in p:
        y = y + p["b"]
    return y


# ------------------------------------------------------------------- MLPs
def init_swiglu(key, d: int, d_ff: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"wi": init_linear(k1, d, d_ff, dtype),
            "wg": init_linear(k2, d, d_ff, dtype),
            "wo": init_linear(k3, d_ff, d, dtype, scale=d_ff ** -0.5)}


def swiglu(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    return linear(p["wo"], jax.nn.silu(linear(p["wg"], x)) * linear(p["wi"], x))


# ------------------------------------------------------- embedding / logits
def init_embed(key, vocab: int, d: int, dtype) -> dict:
    return {"table": truncated_normal(key, (vocab, d), 1.0, dtype)}


def embed(p: dict, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(p["table"], tokens, axis=0)


def unembed(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Logits head; kept separate from the embedding (no tying by default)."""
    return linear(p, x)
