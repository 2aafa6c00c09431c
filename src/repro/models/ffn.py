"""Gated FFN (SwiGLU / GeGLU) and Mixture-of-Experts with dense dispatch.

The MoE uses the GShard-style einsum dispatch/combine so expert weights shard
cleanly over the ``model`` mesh axis (expert parallelism) and the whole layer
stays a single SPMD program — collectives (all-to-all under EP) are emitted by
GSPMD and show up in the roofline's collective term.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import MODEL_AXIS, _dense_init, maybe_axis

Params = Dict[str, Any]


def _act(name: str):
    return jax.nn.silu if name == "silu" else jax.nn.gelu


# ---------------------------------------------------------------------------
# dense FFN
# ---------------------------------------------------------------------------


def init_ffn(key, d: int, d_ff: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _dense_init(ks[0], (d, d_ff), dtype),
        "w_up": _dense_init(ks[1], (d, d_ff), dtype),
        "w_down": _dense_init(ks[2], (d_ff, d), dtype),
    }


def ffn_specs(d_ff: int) -> Params:
    ax = maybe_axis(d_ff, MODEL_AXIS)
    return {"w_gate": P(None, ax), "w_up": P(None, ax), "w_down": P(ax, None)}


def ffn(params: Params, x, act: str = "silu"):
    g = _act(act)(jnp.einsum("bsd,df->bsf", x, params["w_gate"]))
    u = jnp.einsum("bsd,df->bsf", x, params["w_up"])
    return jnp.einsum("bsf,fd->bsd", g * u, params["w_down"])


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------


def init_moe(key, cfg) -> Params:
    m = cfg.moe
    d, f = cfg.d_model, m.d_ff_expert
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(ks[0], (d, m.n_experts), jnp.float32),
        "w_gate": _dense_init(ks[1], (m.n_experts, d, f), dtype),
        "w_up": _dense_init(ks[2], (m.n_experts, d, f), dtype),
        "w_down": _dense_init(ks[3], (m.n_experts, f, d), dtype),
    }
    if m.n_shared:
        p["shared"] = init_ffn(ks[4], d, f * m.n_shared, dtype)
    return p


def moe_specs(cfg) -> Params:
    m = cfg.moe
    e_ax = maybe_axis(m.n_experts, MODEL_AXIS)
    f_ax = maybe_axis(m.d_ff_expert, MODEL_AXIS) if e_ax is None else None
    p = {
        "router": P(None, None),
        "w_gate": P(e_ax, None, f_ax),
        "w_up": P(e_ax, None, f_ax),
        "w_down": P(e_ax, f_ax, None),
    }
    if m.n_shared:
        p["shared"] = ffn_specs(m.d_ff_expert * m.n_shared)
    return p


MOE_GROUP = 1024          # tokens per dispatch group (GShard-style grouping)
MOE_DENSE_T = 256         # below this token count, run the dropless path


def _moe_dense_small(params: Params, cfg, xt, act: str):
    """Dropless path for small token counts (decode steps, tiny batches):
    every expert processes every token, gates zero the non-selected ones.

    Rationale (H2PIPE economics): at decode, a batch of B tokens with
    top-k routing touches ~all experts anyway, so the step is bound by
    expert WEIGHT reads, not FLOPs — computing all experts costs no extra
    HBM traffic and removes the gather/capacity machinery (and its drops)
    entirely.  Exactness also makes serving bit-compatible with training
    for small batches."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, m.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(xt.shape[0])[:, None], top_e].set(top_p)    # [T,E]
    g = _act(act)(jnp.einsum("td,edf->etf", xt, params["w_gate"]))
    u = jnp.einsum("td,edf->etf", xt, params["w_up"])
    ye = jnp.einsum("etf,efd->etd", g * u, params["w_down"])   # [E,T,d]
    y = jnp.einsum("etd,te->td", ye, gates.astype(ye.dtype))
    return y, _aux_loss(probs, top_e, m.n_experts)


def moe_ffn(params: Params, cfg, x, act: str = "silu"):
    """Grouped, gather-based top-k dispatch (scales to 1M-token steps).

    Tokens are split into groups of ~MOE_GROUP (groups shard over the data
    axis); within a group each expert has capacity ceil(cf*Tg*k/E).  Routing
    uses gathers/scatters instead of the GShard one-hot einsum, avoiding the
    O(T*E*C) dispatch tensor and its matmul FLOPs — only O(E*C*d) data
    movement per group.  Overflowing tokens are dropped (capacity factor
    1.25, the paper-standard policy).
    """
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    if T <= MOE_DENSE_T:
        y, aux = _moe_dense_small(params, cfg, xt, act)
        if m.n_shared:
            y = y + ffn(params["shared"], xt[None], act)[0]
        return y.reshape(B, S, d), aux
    tg = min(MOE_GROUP, T)
    assert T % tg == 0, (T, tg)
    G = T // tg
    cap = max(1, int(m.capacity_factor * tg * m.top_k / m.n_experts))
    xg = xt.reshape(G, tg, d)

    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                        params["router"])
    probs = jax.nn.softmax(logits, axis=-1)                    # [G,tg,E]
    top_p, top_e = jax.lax.top_k(probs, m.top_k)               # [G,tg,k]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    if _ep_available(m):
        y = _moe_ep_shardmap(params, cfg, xg, top_p, top_e, cap, act)
        if m.n_shared:
            y = y + ffn(params["shared"], xt[None], act)[0].reshape(
                G, tg, d)
        probs2 = probs.reshape(T, m.n_experts)
        return (y.reshape(B, S, d),
                _aux_loss(probs2, top_e.reshape(T, m.top_k), m.n_experts))

    def route_group(top_e_g, top_p_g, x_g):
        # position of each (token,k) choice within its expert's buffer
        flat_e = top_e_g.reshape(-1)                           # [tg*k]
        onehot = jax.nn.one_hot(flat_e, m.n_experts, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, 0) - onehot)[
            jnp.arange(flat_e.shape[0]), flat_e]               # [tg*k]
        keep = pos < cap
        tok = jnp.arange(flat_e.shape[0]) // m.top_k
        # slot -> token map via scatter (dropped slots point at token 0
        # but are masked by `valid`)
        slot_tok = jnp.zeros((m.n_experts, cap), jnp.int32)
        valid = jnp.zeros((m.n_experts, cap), jnp.bool_)
        e_idx = jnp.where(keep, flat_e, 0)
        c_idx = jnp.where(keep, pos, 0)
        slot_tok = slot_tok.at[e_idx, c_idx].max(
            jnp.where(keep, tok, 0), mode="drop")
        valid = valid.at[e_idx, c_idx].max(keep, mode="drop")
        xe = x_g[slot_tok] * valid[..., None].astype(x_g.dtype)  # [E,C,d]
        gate = jnp.where(keep.reshape(tg, m.top_k), top_p_g, 0.0)
        return xe, gate, pos.reshape(tg, m.top_k)

    xe, gate, pos = jax.vmap(route_group)(top_e, top_p, xg)    # [G,E,C,d]

    g = _act(act)(jnp.einsum("gecd,edf->gecf", xe, params["w_gate"]))
    u = jnp.einsum("gecd,edf->gecf", xe, params["w_up"])
    ye = jnp.einsum("gecf,efd->gecd", g * u, params["w_down"])  # [G,E,C,d]

    def combine_group(ye_g, top_e_g, pos_g, gate_g):
        back = ye_g[top_e_g, jnp.clip(pos_g, 0, cap - 1)]      # [tg,k,d]
        return jnp.einsum("tkd,tk->td", back,
                          gate_g.astype(ye_g.dtype))

    y = jax.vmap(combine_group)(ye, top_e, pos, gate)          # [G,tg,d]
    y = y.reshape(T, d)

    if m.n_shared:
        y = y + ffn(params["shared"], xt[None], act)[0]
    probs2 = probs.reshape(T, m.n_experts)
    return y.reshape(B, S, d), _aux_loss(probs2,
                                         top_e.reshape(T, m.top_k),
                                         m.n_experts)


def _ep_available(m) -> bool:
    """Expert-parallel shard_map path: needs an active multi-device mesh
    whose model axis divides n_experts (EXPERIMENTS.md §Perf HC2)."""
    from repro.models.layers import (_current_physical_mesh, axis_size)
    mesh = _current_physical_mesh()
    return (mesh is not None and "model" in mesh.axis_names
            and axis_size("model") > 1
            and m.n_experts % axis_size("model") == 0)


def _moe_ep_shardmap(params: Params, cfg, xg, top_p, top_e, cap, act):
    """Expert parallelism as a manual shard_map region (HC2-it1).

    The GSPMD gather-combine all-gathers the full [G,E,C,d] expert output
    across the model axis (~63 GB/device/layer on deepseek-v2).  Here each
    model shard computes ONLY its E/model experts locally and the combine
    is a single psum of the [tokens, d] partial output — the collective
    shrinks from E*C*d to d per token.  Routing metadata (top-k, positions,
    keep) is computed outside, replicated over the model axis.

    This is the H2PIPE pseudo-channel assignment at datacenter scale:
    experts (weight-heavy, low duty cycle) live sharded like HBM-offloaded
    kernels, and only the small activation stream crosses the interconnect.
    """
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.models.layers import (_current_physical_mesh, axis_size,
                                     dp_spec)
    m = cfg.moe
    mesh = _current_physical_mesh()
    n_model = axis_size("model")
    E_local = m.n_experts // n_model
    G, tg, d = xg.shape
    k = m.top_k

    # routing positions within each expert's capacity buffer (global,
    # deterministic, replicated across model shards)
    def positions(top_e_g):
        flat_e = top_e_g.reshape(-1)
        onehot = jax.nn.one_hot(flat_e, m.n_experts, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, 0) - onehot)[
            jnp.arange(flat_e.shape[0]), flat_e]
        return pos.reshape(tg, k)

    pos = jax.vmap(positions)(top_e)                       # [G,tg,k]
    keep = pos < cap
    gate = jnp.where(keep, top_p, 0.0)

    dp = dp_spec(G) or None

    def region(w_gate, w_up, w_down, xg_l, te_l, pos_l, gate_l):
        col = jax.lax.axis_index("model")

        def one_group(x_g, te_g, pos_g, gate_g):
            rel = te_g - col * E_local                     # [tg,k]
            mine = (rel >= 0) & (rel < E_local) & (pos_g < cap)
            flat_rel = jnp.where(mine, rel, 0).reshape(-1)
            flat_pos = jnp.where(mine, pos_g, 0).reshape(-1)
            tok = jnp.arange(tg * k) // k
            slot_tok = jnp.zeros((E_local, cap), jnp.int32).at[
                flat_rel, flat_pos].max(
                jnp.where(mine.reshape(-1), tok, 0), mode="drop")
            valid = jnp.zeros((E_local, cap), jnp.bool_).at[
                flat_rel, flat_pos].max(mine.reshape(-1), mode="drop")
            xe = x_g[slot_tok] * valid[..., None].astype(x_g.dtype)
            g = _act(act)(jnp.einsum("ecd,edf->ecf", xe, w_gate))
            u = jnp.einsum("ecd,edf->ecf", xe, w_up)
            ye = jnp.einsum("ecf,efd->ecd", g * u, w_down)  # [E_l,C,d]
            back = ye[jnp.clip(rel, 0, E_local - 1),
                      jnp.clip(pos_g, 0, cap - 1)]          # [tg,k,d]
            w = (gate_g * mine.astype(gate_g.dtype)).astype(back.dtype)
            return jnp.einsum("tkd,tk->td", back, w)

        y_partial = jax.vmap(one_group)(xg_l, te_l, pos_l, gate_l)
        return jax.lax.psum(y_partial, "model")

    g_spec = P(dp, None, None)
    meta_spec = P(dp, None, None)
    fn = jax.shard_map(
        region, mesh=mesh,
        in_specs=(P("model", None, None), P("model", None, None),
                  P("model", None, None), g_spec, meta_spec, meta_spec,
                  meta_spec),
        out_specs=g_spec, check_vma=False)
    return fn(params["w_gate"], params["w_up"], params["w_down"],
              xg, top_e, pos, gate)


def _aux_loss(probs, top_e, n_experts: int):
    """Switch-style load-balancing auxiliary loss."""
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jax.nn.one_hot(top_e[:, 0], n_experts, dtype=jnp.float32), axis=0)
    return n_experts * jnp.sum(me * ce)
