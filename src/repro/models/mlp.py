"""SwiGLU MLP and sort-based top-k MoE (dropping, capacity-bounded).

The MoE dispatch is the production-style sort formulation (MegaBlocks /
MaxText lineage), not the GShard one-hot einsum — the (T*k) assignment sort
plus capacity-bounded scatter keeps the dispatch buffer at (E, C, D) instead
of a (T, E, C) one-hot, which is what makes the 384-expert Kimi-K2 config
compilable and shardable (experts on the "model" axis).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import dense_init


def init_mlp(key, cfg, d_ff: int | None = None) -> dict:
    dt = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d, ff), dt),
        "w_up": dense_init(k2, (d, ff), dt),
        "w_down": dense_init(k3, (ff, d), dt),
    }


def mlp(p: dict, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    return h @ p["w_down"]


def init_moe(key, cfg) -> dict:
    dt = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    e = cfg.n_experts
    ffe = cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e), jnp.float32),
        "w_gate": dense_init(ks[1], (e, d, ffe), dt),
        "w_up": dense_init(ks[2], (e, d, ffe), dt),
        "w_down": dense_init(ks[3], (e, ffe, d), dt),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=ffe * cfg.n_shared_experts)
    return p


def moe(p: dict, x: jax.Array, cfg) -> tuple[jax.Array, jax.Array]:
    """Top-k MoE layer. Returns (output, aux load-balancing loss)."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    if s == 1:
        cap = t  # decode: buffer is tiny, never drop a token
    else:
        cap = min(int(t * k / e * cfg.capacity_factor) + 1, t * k)

    xf = x.reshape(t, d)
    logits = (xf.astype(jnp.float32)) @ p["router"]
    gates = jax.nn.softmax(logits, axis=-1)  # (T, E)
    top_w, top_i = jax.lax.top_k(gates, k)   # (T, k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)

    # aux loss (Switch-style): E * sum_e f_e * p_e
    me = jnp.mean(gates, axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)

    # sort assignments by expert id
    ids = top_i.reshape(-1)                 # (T*k,)
    wts = top_w.reshape(-1)
    order = jnp.argsort(ids)
    ids_s = ids[order]
    tok_s = order // k
    wts_s = wts[order]
    counts = jnp.zeros((e,), jnp.int32).at[ids_s].add(1)
    offsets = jnp.cumsum(counts) - counts   # start of each expert's run
    pos = jnp.arange(t * k) - offsets[ids_s]
    keep = pos < cap
    slot = jnp.where(keep, ids_s * cap + pos, e * cap)  # OOB -> dropped

    buf = jnp.zeros((e * cap, d), x.dtype)
    buf = buf.at[slot].set(xf[tok_s], mode="drop")
    buf = buf.reshape(e, cap, d)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    y = jnp.einsum("ecf,efd->ecd", h, p["w_down"]).reshape(e * cap, d)

    gathered = jnp.take(y, jnp.minimum(slot, e * cap - 1), axis=0)
    gathered = gathered * (wts_s * keep).astype(x.dtype)[:, None]
    out = jnp.zeros((t, d), x.dtype).at[tok_s].add(gathered)

    if "shared" in p:
        out = out + mlp(p["shared"], xf)
    return out.reshape(b, s, d), aux


def moe_ep(p: dict, x: jax.Array, cfg, mesh, batch_axes: tuple, tp_axis: str = "model"):
    """Expert-parallel MoE via shard_map (§Perf-E1, the kimi-cell fix).

    Exploits the framework's layout invariant: activations are replicated
    across the "model" axis while experts are sharded over it. Each model
    rank therefore already holds every token — dispatch is a purely LOCAL
    select of the tokens routed to its resident experts, and combining is a
    single psum over the model axis (each token's expert outputs live on
    exactly the ranks that own those experts; everyone else contributes
    zero). Total MoE comm = one activation-sized all-reduce per layer —
    no all-to-all, no cross-rank scatter.
    """
    from jax.sharding import PartitionSpec as P

    e, k = cfg.n_experts, cfg.top_k
    n_tp = mesh.shape[tp_axis]
    assert e % n_tp == 0, (e, n_tp)
    e_loc = e // n_tp

    def local(xb, router, wg, wu, wd, shared_p):
        # xb: (B_loc, S, D) — replicated over tp; wg/wu/wd: (E_loc, ...)
        bl, s, d = xb.shape
        t = bl * s
        xf = xb.reshape(t, d)
        logits = xf.astype(jnp.float32) @ router
        gates = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = jax.lax.top_k(gates, k)
        top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)

        me = jnp.mean(gates, axis=0)
        ce = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(1.0) / (t * k)
        aux = e * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, tp_axis)

        # keep only assignments owned by this model rank
        rank = jax.lax.axis_index(tp_axis)
        lo = rank * e_loc
        ids = top_i.reshape(-1)
        wts = top_w.reshape(-1)
        mine = jnp.logical_and(ids >= lo, ids < lo + e_loc)
        ids_l = jnp.where(mine, ids - lo, e_loc)  # e_loc = drop bucket
        cap = max(int(t * k / e * cfg.capacity_factor) + 1, 4) if s > 1 else t

        order = jnp.argsort(ids_l)  # drops sort to the end
        ids_s = ids_l[order]
        tok_s = order // k
        wts_s = wts[order]
        counts = jnp.zeros((e_loc + 1,), jnp.int32).at[ids_s].add(1)
        offsets = jnp.cumsum(counts) - counts
        pos = jnp.arange(t * k) - offsets[ids_s]
        keep = jnp.logical_and(ids_s < e_loc, pos < cap)
        slot = jnp.where(keep, ids_s * cap + pos, e_loc * cap)

        buf = jnp.zeros((e_loc * cap, d), xb.dtype)
        buf = buf.at[slot].set(xf[tok_s], mode="drop").reshape(e_loc, cap, d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
        h = h * jnp.einsum("ecd,edf->ecf", buf, wu)
        y = jnp.einsum("ecf,efd->ecd", h, wd).reshape(e_loc * cap, d)
        gathered = jnp.take(y, jnp.minimum(slot, e_loc * cap - 1), axis=0)
        gathered = gathered * (wts_s * keep).astype(xb.dtype)[:, None]
        out = jnp.zeros((t, d), xb.dtype).at[tok_s].add(gathered)
        if shared_p is not None:
            # shared expert: every rank holds the tokens; scale by 1/n_tp so
            # the combining psum reconstructs a single contribution
            out = out + (mlp(shared_p, xf) / n_tp).astype(out.dtype)
        out = jax.lax.psum(out, tp_axis)  # combine expert contributions
        return out.reshape(bl, s, d), aux

    ba = batch_axes
    shared = p.get("shared")
    in_specs = (
        P(ba, None, None),
        P(None, None),                     # router replicated
        P(tp_axis, None, None),            # expert weights: E over tp
        P(tp_axis, None, None),
        P(tp_axis, None, None),
        None if shared is None else jax.tree.map(lambda _: P(None, None), shared),
    )
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(ba, None, None), P()),
        check_vma=False,
    )
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"], shared)
