"""Mixture-of-Experts: top-k routing with capacity, sort-based dispatch.

Dispatch is **scatter/gather based** (sort tokens by expert, place into an
(E, C, d) buffer, batched expert matmul, weighted gather back) rather than
the GShard one-hot-einsum formulation: the one-hot dispatch contraction
costs O(T^2 d) *real* MXU FLOPs (it corrupts both the roofline and actual
hardware utilization), while scatter/gather is memory-bound data movement
XLA lowers to dynamic-slice/scatter + the EP all-to-alls.

Experts shard over the "model" mesh axis (EP); tokens stay batch-sharded —
the cross-shard movement materializes as all-to-all/all-gather collectives
in the compiled dry-run, which §Roofline accounts explicitly.

Aux load-balancing loss follows Switch/GShard: E * sum_e f_e * p_e.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import truncated_normal

__all__ = ["init_moe", "moe_forward"]


def init_moe(key, cfg) -> dict:
    d, E, dff = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    ks = jax.random.split(key, 5)
    p = {
        "router": {"w": truncated_normal(ks[0], (d, E), d ** -0.5, jnp.float32)},
        "wi": truncated_normal(ks[1], (E, d, dff), d ** -0.5, dt),
        "wg": truncated_normal(ks[2], (E, d, dff), d ** -0.5, dt),
        "wo": truncated_normal(ks[3], (E, dff, d), dff ** -0.5, dt),
    }
    if cfg.n_shared_experts:
        from .layers import init_swiglu
        p["shared"] = init_swiglu(ks[4], d, cfg.n_shared_experts * dff, dt)
    return p


def _dispatch_groups(cfg) -> int:
    """GShard-style dispatch group count = DP shard count: every group's
    sort/cumsum/scatter stays local to its shard (no cross-device gathers),
    and the only cross-shard movement is the expert einsum's TP collectives."""
    sizes = dict(jax.sharding.get_abstract_mesh().shape)
    return max(sizes.get("pod", 1) * sizes.get("data", 1), 1)


def moe_forward(p: dict, cfg, x: jnp.ndarray):
    """x: (B, L, d) -> (y, aux_loss)."""
    from .layers import maybe_constrain
    B, L, d = x.shape
    E, topk = cfg.n_experts, cfg.moe_top_k
    T = B * L
    G = _dispatch_groups(cfg)
    while T % G:
        G //= 2
    G = max(G, 1)
    Tg = T // G
    xt = maybe_constrain(x.reshape(G, Tg, d), "data", None, None)

    logits = jnp.einsum("gtd,de->gte", xt.astype(jnp.float32), p["router"]["w"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, topk)                  # (G, Tg, topk)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # Switch-style aux loss (global statistics)
    f = jnp.zeros((E,), jnp.float32).at[experts.reshape(-1)].add(1.0) / (T * topk)
    pbar = probs.mean(axis=(0, 1))
    aux = cfg.router_aux_coef * E * jnp.sum(f * pbar)

    C = max(int(Tg * topk * cfg.capacity_factor / E), 4)

    def dispatch_one(xg, eg, gg):
        """One group: local sort-by-expert + capacity scatter."""
        flat_e = eg.reshape(-1)                                  # (Tg*topk,)
        flat_g = gg.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(Tg), topk)
        order = jnp.argsort(flat_e)
        e_s, g_s, t_s = flat_e[order], flat_g[order], flat_t[order]
        counts = jnp.zeros((E,), jnp.int32).at[e_s].add(1)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(Tg * topk) - starts[e_s]
        keep = pos < C
        slot = e_s * C + jnp.where(keep, pos, 0)
        buf = jnp.zeros((E * C, d), xg.dtype)
        buf = buf.at[slot].add(jnp.where(keep[:, None], xg[t_s], 0))
        return buf.reshape(E, C, d), (slot, t_s, g_s, keep)

    h, meta = jax.vmap(dispatch_one)(xt, experts, gates)         # (G, E, C, d)
    h = maybe_constrain(h, "data", "model", None, None)
    # batched expert SwiGLU: real FLOPs 2*G*E*C*d*dff per matmul; the ff/d
    # contraction dims carry the "model" sharding of the expert weights (TP
    # inside each expert), so compute splits over data x model.
    y = jnp.einsum("gecd,edf->gecf", h, p["wi"]) * jax.nn.silu(
        jnp.einsum("gecd,edf->gecf", h, p["wg"]))
    y = jnp.einsum("gecf,efd->gecd", y, p["wo"])
    y = maybe_constrain(y, "data", "model", None, None)

    def combine_one(yg, m):
        slot, t_s, g_s, keep = m
        contrib = jnp.where(keep[:, None],
                            yg.reshape(E * C, d)[slot] * g_s[:, None].astype(yg.dtype), 0)
        return jnp.zeros((Tg, d), yg.dtype).at[t_s].add(contrib)

    out = jax.vmap(combine_one)(y, meta).astype(x.dtype)         # (G, Tg, d)
    out = maybe_constrain(out, "data", None, None)

    if "shared" in p:
        from .layers import swiglu
        out = out + swiglu(p["shared"], xt)
    return out.reshape(B, L, d), aux
