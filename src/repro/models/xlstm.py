"""xLSTM blocks: mLSTM (matrix memory) and sLSTM (scalar memory, exp gating).

mLSTM is linear-attention-like and has no hidden-to-gate recurrence, so it
runs in the chunkwise-parallel form (arXiv:2405.04517, appendix): within a
chunk of ``CHUNK`` tokens the outputs are dense MXU matmuls, (QKᵀ ⊙ D)V with
D the products of the stabilized exponential gates, and a short ``lax.scan`` over the chunks
carries the matrix memory, so C is read and written once a chunk, not once a
token. The chunk length follows the sequence: decode (S = 1) is one chunk of
one token, the single-step recurrence. sLSTM has true recurrent gate
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
# mLSTM chunk length: a sequence of at most CHUNK tokens is one chunk
CHUNK = 128
# the stabilizer's start: finite, so no −∞ − (−∞)
NEG = -1e30


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


def _mlstm_chunk(state, chunk):
    """One chunk of the chunkwise-parallel mLSTM: the chunk's outputs and the
    state after it, the same function as a step per token in another order.

    The stabilizer m_t = max(log σ(f_t) + m_{t−1}, i_t) and the stabilized
    gates i'_t = e^{i_t − m_t}, f'_t = e^{log σ(f_t) + m_{t−1} − m_t} are
    the recurrent step's, token by token on (B, H) scalars. With
    D_ts = i'_s Π_{s<j≤t} f'_j (s ≤ t) and g_t = Π_{j≤t} f'_j,
    h_t = [g_t·q_t C_prev + Σ_s D_ts (q_t·k_s) v_s]
          / max(|g_t·q_t·n_prev + Σ_s D_ts (q_t·k_s)|, e^{−m_t}):
    the sums run on the MXU as (QKᵀ ⊙ D)V.
    """
    c_mat, n_vec, m = state              # (B,H,dh,dh), (B,H,dh), (B,H)
    q, k, v, i_raw, f_raw = chunk        # (B,H,L,dh) ×3, (B,H,L) ×2
    length = q.shape[2]
    causal = jnp.tril(jnp.ones((length, length), bool))
    log_f = jax.nn.log_sigmoid(f_raw)

    def stabilizer(m, t):
        log_f_t, i_t = t
        m = jnp.maximum(log_f_t + m, i_t)
        return m, m

    _, m_t = jax.lax.scan(stabilizer, m, (jnp.moveaxis(log_f, -1, 0),
                                          jnp.moveaxis(i_raw, -1, 0)))
    m_t = jnp.moveaxis(m_t, 0, -1)                              # (B,H,L)
    m_before = jnp.concatenate([m[..., None], m_t[..., :-1]], axis=-1)
    i_g = jnp.exp(i_raw - m_t)
    f_g = jnp.exp(log_f + m_before - m_t)
    # [t, s] = Π_{s<j≤t} f'_j as a masked cumulative product
    decay = jnp.cumprod(
        jnp.where(jnp.tril(causal, -1), f_g[..., :, None], 1.0), axis=-2)
    decay = jnp.where(causal, decay * i_g[..., None, :], 0.0)   # (B,H,L,L)
    g = jnp.cumprod(f_g, axis=-1)       # the weight of the state before
    w = jnp.einsum("bhtd,bhsd->bhts", q, k) * decay
    num = (g[..., None] * jnp.einsum("bhtd,bhde->bhte", q, c_mat)
           + jnp.einsum("bhts,bhse->bhte", w, v))
    den = g * jnp.einsum("bhtd,bhd->bht", q, n_vec) + w.sum(-1)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))[..., None]
    # the state at the chunk's last token
    g_end, w_end = g[..., -1], decay[..., -1, :]
    c_mat = (g_end[..., None, None] * c_mat
             + jnp.einsum("bhsd,bhse->bhde", w_end[..., None] * k, v))
    n_vec = g_end[..., None] * n_vec + jnp.einsum("bhs,bhsd->bhd", w_end, k)
    return (c_mat, n_vec, m_t[..., -1]), h


def _mlstm_seq(state, seqs):
    """The mLSTM over a whole sequence: (q, k, v, i, f) in (B,H,S,...)
    layouts → (the state after it, h (B,H,S,dh)). Whole chunks of
    ``min(CHUNK, S)`` tokens run through a scan, then the last chunk (the
    remainder, 1..L tokens) on its own, so that under jit a last state
    nobody reads is dropped."""
    b, h, s, dh = seqs[0].shape
    length = min(CHUNK, s)
    split = (s - 1) // length * length

    def chunks(a):                       # (B,H,S,...) → (S/L−1, B,H,L,...)
        a = a[:, :, :split].reshape(a.shape[:2] + (split // length, length)
                                     + a.shape[3:])
        return jnp.moveaxis(a, 2, 0)

    state, hs = jax.lax.scan(_mlstm_chunk, state, tuple(map(chunks, seqs)))
    state, h_last = _mlstm_chunk(state, tuple(a[:, :, split:] for a in seqs))
    return state, jnp.concatenate(
        [jnp.moveaxis(hs, 0, 2).reshape(b, h, split, dh), h_last], axis=2)


def mlstm_apply(p, x, num_heads, *, init_state=None, return_state=False):
    """x: (B, S, D) → (B, S, D)."""
    b, s, d_model = x.shape
    d_inner, dh = _inner(d_model, num_heads)
    up = x @ p["up"]
    x_in, gate = up[..., :d_inner], up[..., d_inner:]
    qkv = (x_in @ p["qkv"]).astype(jnp.float32).reshape(b, s, 3, num_heads, dh)
    q, k, v = (qkv[:, :, j].transpose(0, 2, 1, 3) for j in range(3))  # (B,H,S,dh)
    k = k / math.sqrt(dh)
    if_g = (x_in @ p["if_proj"]).astype(jnp.float32).reshape(b, s, 2, num_heads)
    i_raw, f_raw = (if_g[:, :, j].transpose(0, 2, 1) for j in range(2))  # (B,H,S)

    st = init_mlstm_state(b, d_model, num_heads) if init_state is None else init_state
    state = (st["c"], st["n"], st["m"])
    with jax.named_scope(scopes.SEQMIX):
        state, h = _mlstm_seq(state, (q, k, v, i_raw, f_raw))
    h = h.transpose(0, 2, 1, 3).reshape(b, s, d_inner)
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
        "m": jnp.full((batch, num_heads), NEG, jnp.float32),
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
