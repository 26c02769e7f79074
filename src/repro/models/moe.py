"""Mixture-of-Experts layer (top-k router, capacity-based grouped dispatch).

Dispatch uses the classic one-hot combine tensors, but over token *groups* so
the dispatch einsums stay linear in total tokens (cost ≈ k·cf·g per token,
negligible vs the expert FLOPs — see DESIGN.md). Experts are laid out on a
leading E dim so the expert weights shard over the mesh
(E → expert-parallel submesh when enabled, else tensor-parallel inner dims).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.config import MoEConfig
from repro.core import act
from repro.models import layers as L


def init_moe(key, d_model, d_ff, moe: MoEConfig, activation, dtype):
    ks = jax.random.split(key, 4)
    e = moe.num_experts

    def ed(k, d_in, d_out):
        flat = L.dense_init(k, d_in, e * d_out, dtype)
        return flat.reshape(d_in, e, d_out).transpose(1, 0, 2)  # (E, d_in, d_out)

    p = {
        "router": L.dense_init(ks[0], d_model, e, dtype),
        "w_up": ed(ks[1], d_model, d_ff),
        "w_down": ed(ks[2], d_ff, d_model),
    }
    if activation == "swiglu":
        p["w_gate"] = ed(ks[3], d_model, d_ff)
    return p


def _expert_hidden(p, h_in, activation):
    """(n, E, cap, d) → (n, E, cap, f): up/gate projection + nonlinearity."""
    up = jnp.einsum("necd,edf->necf", h_in, p["w_up"])
    if activation == "swiglu":
        gate = jnp.einsum("necd,edf->necf", h_in, p["w_gate"])
        return jax.nn.silu(gate) * up
    if activation == "relu2":
        return jnp.square(jax.nn.relu(up))
    return jax.nn.gelu(up)


def _scatter_dispatch(groups, slot, e, cap):
    """Scatter token vectors into expert-capacity slots (§Perf granite iter 1).

    groups (ng, g, d), slot (ng, g, k) flat indices into [0, e·cap] (e·cap =
    the drop bin). Replaces the one-hot dispatch einsum, whose (g × E × cap)
    cross tensors cost ~e/k× the dispatched-token bytes (granite, 40 experts
    top-8: ≈1 PB-scale intermediates at train_4k). Slots are unique per
    (group, expert, position) by cumsum construction, so the scatter-add is
    collision-free and exactly equals the einsum dispatch.
    """
    ng, g, d = groups.shape
    k = slot.shape[-1]
    src = jnp.broadcast_to(groups[:, :, None, :], (ng, g, k, d))
    src = src.reshape(ng, g * k, d)
    flat = slot.reshape(ng, g * k)
    buf = jnp.zeros((ng, e * cap + 1, d), groups.dtype)
    buf = buf.at[jnp.arange(ng)[:, None], flat].add(src)
    return buf[:, : e * cap].reshape(ng, e, cap, d)


def _gather_combine(out_e, slot, weight):
    """Inverse of _scatter_dispatch: gather each token's expert output and
    weight by its router prob. out_e (ng, e, cap, d); slot/weight (ng, g, k)."""
    ng, e, cap, d = out_e.shape
    g, k = slot.shape[1], slot.shape[2]
    flat = out_e.reshape(ng, e * cap, d)
    flat = jnp.concatenate([flat, jnp.zeros((ng, 1, d), flat.dtype)], axis=1)
    gath = jnp.take_along_axis(
        flat, slot.reshape(ng, g * k)[..., None], axis=1)
    gath = gath.reshape(ng, g, k, d)
    return jnp.sum(gath * weight[..., None], axis=2)


def _ffn_dense(p, groups, slot, weight, e, cap, activation):
    """Single-program expert FFN (GSPMD chooses the collectives).

    NOTE (§Perf grok iteration 1, refuted): constraining hidden to f-sharded
    and/or out_e to d-sharded here makes GSPMD reshard the dispatched tensors
    and collective traffic explodes ~6×. GSPMD's unconstrained placement
    (partial-sum all-reduce of out_e in dispatched-token space, 2.5× token
    volume at capacity 1.25 × top-2) is the best this path expresses; the
    combine-before-reduce placement needs _ffn_shard_map.
    """
    h_in = _scatter_dispatch(groups, slot, e, cap)
    hidden = _expert_hidden(p, h_in, activation)
    out_e = jnp.einsum("necf,efd->necd", hidden, p["w_down"])
    return _gather_combine(out_e, slot, weight)


def _shard_map_ok(ng: int, d_ff: int) -> bool:
    """Use the explicit shard_map FFN when the policy is active and the
    group/feature dims divide the federation/model axes.
    REPRO_MOE_FFN=dense forces the GSPMD path (perf A/B)."""
    import os
    if os.environ.get("REPRO_MOE_FFN") == "dense":
        return False
    pol = act._POLICY.get()
    if pol is None:
        return False
    import math as _math
    fsdp = _math.prod(pol["mesh"].shape[a] for a in pol["batch"])
    tp = _math.prod(pol["mesh"].shape[a] for a in pol["model"])
    return tp > 1 and ng % max(fsdp, 1) == 0 and d_ff % tp == 0


def _ffn_shard_map(p, groups, slot, weight, e, cap, activation):
    """Expert FFN with an explicit collective schedule (§Perf grok iter 2):

    tokens stay sharded over the federation axes; expert weights enter
    d_ff-sharded over 'model'; dispatch/FFN/combine are local; the combine
    runs on the *partial* (f-shard) expert outputs — linearity lets it
    commute with the f-reduction — and ONE psum in token space (ng·g·d)
    finishes the layer. vs the dense path's all-reduce in dispatched-token
    space this moves 1/(top_k·capacity_factor) of the bytes (grok: 2.5×).
    """
    from jax.sharding import PartitionSpec as P

    pol = act._POLICY.get()
    mesh, fsdp, tp = pol["mesh"], pol["batch"], pol["model"]
    tok_spec = P(fsdp)  # ng dim; g/k/d replicated
    wcol = P(None, None, tp)   # (E, d, f): f over model
    wrow = P(None, tp, None)   # (E, f, d)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(
            {k: (wrow if k == "w_down" else wcol)
             for k in ("w_up", "w_down", *(("w_gate",) if "w_gate" in p else ()))},
            tok_spec, tok_spec, tok_spec,
        ),
        out_specs=tok_spec,
    )
    def ffn(weights, groups_l, slot_l, weight_l):
        h_in = _scatter_dispatch(groups_l, slot_l, e, cap)
        hidden = _expert_hidden(weights, h_in, activation)
        out_partial = jnp.einsum("necf,efd->necd", hidden, weights["w_down"])
        out_l = _gather_combine(out_partial, slot_l, weight_l)
        return jax.lax.psum(out_l, tp)

    weights = {k: p[k] for k in ("w_up", "w_down", "w_gate") if k in p}
    return ffn(weights, groups, slot, weight)


def moe_apply(p, x, moe: MoEConfig, activation):
    """x: (B, S, D) → (B, S, D); also returns the router aux loss (load-balance)."""
    b, s, d = x.shape
    e, k = moe.num_experts, moe.top_k
    tokens = x.reshape(-1, d)
    t = tokens.shape[0]
    g = min(moe.group_size, t)
    ng = -(-t // g)
    pad = ng * g - t
    if pad:
        tokens = jnp.pad(tokens, ((0, pad), (0, 0)))
    groups = tokens.reshape(ng, g, d)

    logits = groups @ p["router"]                       # (ng, g, E)
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
    top_p, top_i = jax.lax.top_k(probs, k)              # (ng, g, k)
    top_p = top_p / jnp.clip(top_p.sum(-1, keepdims=True), 1e-9)

    cap = int(max(1, round(g * k / e * moe.capacity_factor)))
    # one-hot expert assignment (ng, g, k, E) — position of each (token, k)
    # within its expert queue via cumsum over the flattened (g·k) order
    assign = jax.nn.one_hot(top_i, e, dtype=jnp.float32)
    pos = jnp.cumsum(assign.reshape(ng, g * k, e), axis=1).reshape(ng, g, k, e)
    pos = pos * assign - 1.0
    pos_sel = jnp.max(pos, axis=-1)                 # (ng, g, k): own-expert pos
    keep = (pos_sel >= 0) & (pos_sel < cap)
    # flat slot index into (E·cap); dropped tokens land in the overflow bin
    slot = top_i * cap + pos_sel.astype(jnp.int32)
    slot = jnp.where(keep, slot, e * cap)
    weight = jnp.where(keep, top_p, 0.0)            # (ng, g, k)

    # n=group, g=token-in-group, e=expert, c=capacity slot, d/f=features
    if _shard_map_ok(ng, p["w_up"].shape[-1]):
        out = _ffn_shard_map(p, groups, slot, weight, e, cap, activation)
    else:
        out = _ffn_dense(p, groups, slot, weight, e, cap, activation)
    out = out.reshape(-1, d)[:t].reshape(b, s, d).astype(x.dtype)

    # load-balance aux (Switch-style): E * Σ_e f_e · P_e
    frac_tokens = jnp.mean(assign.sum(2), axis=(0, 1)) / k
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return out, aux
