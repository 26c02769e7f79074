"""xLSTM blocks: mLSTM (matrix memory) and sLSTM (scalar memory, exp gating).

mLSTM is linear-attention-like and has no hidden-to-gate recurrence, so its
training/prefill form here is a ``lax.scan`` over time with stabilized
exponential gating (chunkwise-parallelization is a recorded §Perf candidate);
decode is the same single-step recurrence. sLSTM has true recurrent gate
connections (R · h_{t-1}) and is inherently sequential — scan over time.

Per the assigned config (d_ff=0) the blocks are projection-only: an up
projection (factor 2), the recurrent mixer, and a down projection; no separate
FFN stack. State layouts:
  mLSTM: C (B, H, dh, dh), n (B, H, dh), m (B, H)
  sLSTM: c, n, h (B, H, dh), m (B, H)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro import scopes
from repro.models import layers as L

UP_FACTOR = 2


def _inner(d_model, num_heads):
    d_inner = UP_FACTOR * d_model
    dh = d_inner // num_heads
    return d_inner, dh


# ------------------------------------------------------------------- mLSTM
def init_mlstm(key, d_model, num_heads, dtype):
    d_inner, dh = _inner(d_model, num_heads)
    ks = jax.random.split(key, 4)
    return {
        "up": L.dense_init(ks[0], d_model, 2 * d_inner, dtype),   # [x_in, gate]
        "qkv": L.dense_init(ks[1], d_inner, 3 * d_inner, dtype),
        "if_proj": L.dense_init(ks[2], d_inner, 2 * num_heads, dtype),
        "down": L.dense_init(ks[3], d_inner, d_model, dtype),
    }


def _mlstm_step(carry, inp):
    c_mat, n_vec, m = carry                     # (B,H,dh,dh), (B,H,dh), (B,H)
    q, k, v, i_raw, f_raw = inp                 # (B,H,dh) ×3, (B,H) ×2
    log_f = jax.nn.log_sigmoid(f_raw)
    m_new = jnp.maximum(log_f + m, i_raw)
    i_g = jnp.exp(i_raw - m_new)
    f_g = jnp.exp(log_f + m - m_new)
    c_mat = f_g[..., None, None] * c_mat + i_g[..., None, None] * (
        k[..., :, None] * v[..., None, :]
    )
    n_vec = f_g[..., None] * n_vec + i_g[..., None] * k
    denom = jnp.maximum(
        jnp.abs(jnp.einsum("bhd,bhd->bh", n_vec, q)), jnp.exp(-m_new)
    )
    h = jnp.einsum("bhd,bhde->bhe", q, c_mat) / denom[..., None]
    return (c_mat, n_vec, m_new), h


def mlstm_apply(p, x, num_heads, *, init_state=None, return_state=False):
    """x: (B, S, D) → (B, S, D)."""
    b, s, d_model = x.shape
    d_inner, dh = _inner(d_model, num_heads)
    up = x @ p["up"]
    x_in, gate = up[..., :d_inner], up[..., d_inner:]
    qkv = (x_in @ p["qkv"]).astype(jnp.float32)
    q, k, v = jnp.split(qkv.reshape(b, s, 3, num_heads, dh), 3, axis=2)
    q, k, v = (a[:, :, 0].transpose(1, 0, 2, 3) for a in (q, k, v))  # (S,B,H,dh)
    k = k / math.sqrt(dh)
    if_g = (x_in @ p["if_proj"]).astype(jnp.float32).reshape(b, s, 2, num_heads)
    i_raw = if_g[:, :, 0].transpose(1, 0, 2)                         # (S,B,H)
    f_raw = if_g[:, :, 1].transpose(1, 0, 2)

    if init_state is None:
        state = (
            jnp.zeros((b, num_heads, dh, dh), jnp.float32),
            jnp.zeros((b, num_heads, dh), jnp.float32),
            jnp.full((b, num_heads), -1e30, jnp.float32),
        )
    else:
        state = (init_state["c"], init_state["n"], init_state["m"])
    with jax.named_scope(scopes.SEQMIX):
        state, hs = jax.lax.scan(_mlstm_step, state, (q, k, v, i_raw, f_raw))
    h = hs.transpose(1, 0, 2, 3).reshape(b, s, d_inner)
    out = (h.astype(x.dtype) * jax.nn.silu(gate)) @ p["down"]
    if return_state:
        return out, {"c": state[0], "n": state[1], "m": state[2]}
    return out


def mlstm_decode(p, x, state, num_heads):
    out, new_state = mlstm_apply(
        p, x, num_heads, init_state=state, return_state=True
    )
    return out, new_state


def init_mlstm_state(batch, d_model, num_heads):
    d_inner, dh = _inner(d_model, num_heads)
    return {
        "c": jnp.zeros((batch, num_heads, dh, dh), jnp.float32),
        "n": jnp.zeros((batch, num_heads, dh), jnp.float32),
        "m": jnp.full((batch, num_heads), -1e30, jnp.float32),
    }


# ------------------------------------------------------------------- sLSTM
def init_slstm(key, d_model, num_heads, dtype):
    d_inner, dh = _inner(d_model, num_heads)
    ks = jax.random.split(key, 4)
    return {
        "up": L.dense_init(ks[0], d_model, 2 * d_inner, dtype),
        "wx": L.dense_init(ks[1], d_inner, 4 * d_inner, dtype),      # z,i,f,o
        # block-diagonal (per-head) recurrent kernel for the 4 gates
        "r": (jax.random.normal(ks[2], (4, num_heads, dh, dh), jnp.float32)
              / math.sqrt(dh)).astype(dtype),
        "down": L.dense_init(ks[3], d_inner, d_model, dtype),
    }


def _slstm_step(p_r, carry, inp, num_heads, dh):
    c, n, h, m = carry                               # (B,H,dh)×3, (B,H)
    wx_t = inp                                        # (B, 4, H, dh)
    rec = jnp.einsum("ghde,bhd->bghe", p_r.astype(jnp.float32), h)
    pre = wx_t + rec                                  # (B,4,H,dh)
    z = jnp.tanh(pre[:, 0])
    i_raw = pre[:, 1].mean(-1)                        # scalar gates per head
    f_raw = pre[:, 2].mean(-1)
    o = jax.nn.sigmoid(pre[:, 3])
    log_f = jax.nn.log_sigmoid(f_raw)
    m_new = jnp.maximum(log_f + m, i_raw)
    i_g = jnp.exp(i_raw - m_new)
    f_g = jnp.exp(log_f + m - m_new)
    c = f_g[..., None] * c + i_g[..., None] * z
    n = f_g[..., None] * n + i_g[..., None]
    h_new = o * c / jnp.maximum(n, 1e-6)
    return (c, n, h_new, m_new), h_new


def slstm_apply(p, x, num_heads, *, init_state=None, return_state=False):
    b, s, d_model = x.shape
    d_inner, dh = _inner(d_model, num_heads)
    up = x @ p["up"]
    x_in, gate = up[..., :d_inner], up[..., d_inner:]
    wx = (x_in @ p["wx"]).astype(jnp.float32).reshape(b, s, 4, num_heads, dh)
    wx = wx.transpose(1, 0, 2, 3, 4)                  # (S,B,4,H,dh)
    if init_state is None:
        zeros = jnp.zeros((b, num_heads, dh), jnp.float32)
        state = (zeros, zeros, zeros, jnp.full((b, num_heads), -1e30, jnp.float32))
    else:
        state = (init_state["c"], init_state["n"], init_state["h"], init_state["m"])
    step = lambda carry, inp: _slstm_step(p["r"], carry, inp, num_heads, dh)
    with jax.named_scope(scopes.SEQMIX):
        state, hs = jax.lax.scan(step, state, wx)
    h = hs.transpose(1, 0, 2, 3).reshape(b, s, d_inner)
    out = (h.astype(x.dtype) * jax.nn.silu(gate)) @ p["down"]
    if return_state:
        return out, {"c": state[0], "n": state[1], "h": state[2], "m": state[3]}
    return out


def slstm_decode(p, x, state, num_heads):
    return slstm_apply(p, x, num_heads, init_state=state, return_state=True)


def init_slstm_state(batch, d_model, num_heads):
    d_inner, dh = _inner(d_model, num_heads)
    zeros = jnp.zeros((batch, num_heads, dh), jnp.float32)
    return {"c": zeros, "n": zeros, "h": zeros,
            "m": jnp.full((batch, num_heads), -1e30, jnp.float32)}
