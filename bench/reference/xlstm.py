"""Plain reference of the xLSTM backbone (arXiv:2405.04517) as the program
builds it: a stack of pre-norm residual blocks, ``slstm_every − 1`` mLSTM
blocks then one sLSTM block per group, a final RMS norm, mean pooling.

Each block: RMS norm → up projection to ``2·up_factor·d`` split into the
mixer input and a gate → recurrent mixer → ``h · silu(gate)`` → down
projection back to ``d``. Departures from the paper, as in the program: no
causal convolution, no per-head group norm and no learnable skip in the
blocks; the sLSTM's input and forget gates are per head, the mean of their
``dh`` pre-activations; the mLSTM keys are scaled by ``1/√dh``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import precision as P

F32 = jnp.float32


def _dims(cfg):
    d, h = cfg["d_model"], cfg["num_heads"]
    d_inner = cfg["up_factor"] * d
    g = cfg["slstm_every"]
    groups = cfg["num_layers"] // g
    return d, h, d_inner, d_inner // h, g, groups, cfg["num_layers"] - groups * g


def init(cfg: dict, key) -> dict:
    """Weights from ``key``: normal(0, 1/fan_in) projections, normal(0, 0.02)
    embeddings, unit norm scales, in the configuration's dtype."""
    d, h, di, dh, g, groups, tail = _dims(cfg)
    dt = jnp.dtype(cfg["dtype"])
    counter = iter(range(1 << 20))

    def normal(shape, scale):
        k = jax.random.fold_in(key, next(counter))
        return (jax.random.normal(k, shape, F32) * scale).astype(dt)

    def block(lead, kind):
        mixer = {"up": normal(lead + (d, 2 * di), d ** -0.5)}
        if kind == "m":
            mixer["qkv"] = normal(lead + (di, 3 * di), di ** -0.5)
            mixer["if_proj"] = normal(lead + (di, 2 * h), di ** -0.5)
        else:
            mixer["wx"] = normal(lead + (di, 4 * di), di ** -0.5)
            mixer["r"] = normal(lead + (4, h, dh, dh), dh ** -0.5)
        mixer["down"] = normal(lead + (di, d), di ** -0.5)
        return {"ln": {"scale": jnp.ones(lead + (d,), dt)}, "mixer": mixer}

    params = {"embed": normal((cfg["vocab_size"], d), 0.02),
              "final_norm": {"scale": jnp.ones((d,), dt)}}
    if groups:
        params["mlstm_groups"] = block((groups, g - 1), "m")
        params["slstm"] = block((groups,), "s")
    if tail:
        params["mlstm_tail"] = block((tail,), "m")
    return params


def forward_ops(cfg: dict, seq: int) -> float:
    """Operations of the forward for one sequence of ``seq`` tokens: the
    projections of every block and, per head, the recurrent memory's work,
    all per token (the embedding lookup, norms and pooling not counted)."""
    d, h, L = cfg["d_model"], cfg["num_heads"], cfg["num_layers"]
    di = cfg["up_factor"] * d
    dh = di // h
    n_s = L // cfg["slstm_every"]
    n_m = L - n_s
    m_params = d * 2 * di + di * 3 * di + di * 2 * h + di * d
    s_params = d * 2 * di + di * 4 * di + di * d
    # mLSTM matrix memory per head: the update C ← f·C + i·k vᵀ (one
    # multiply-add per entry) and the readout qᵀC (one per entry)
    m_memory = 2 * dh * dh + 2 * dh * dh
    # sLSTM recurrent gates: R (4, H, dh, dh) against h_{t-1}
    s_recurrent = 2 * 4 * dh * dh
    per_token = (2 * (n_m * m_params + n_s * s_params)
                 + h * (n_m * m_memory + n_s * s_recurrent))
    return per_token * seq


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _stabilized_gates(i_raw, f_raw, m):
    log_f = jax.nn.log_sigmoid(f_raw)
    m_new = jnp.maximum(log_f + m, i_raw)
    return jnp.exp(i_raw - m_new), jnp.exp(log_f + m - m_new), m_new


def _mlstm(p, x, h, kind):
    b, s, d = x.shape
    di = p["down"].shape[0]
    dh = di // h
    up = P.matmul(x, p["up"], kind)
    x_in, gate = up[..., :di], up[..., di:]
    qkv = P.matmul(x_in, p["qkv"], kind).reshape(b, s, 3, h, dh)
    q, k, v = (jnp.moveaxis(qkv[:, :, j], 1, 0) for j in range(3))  # (S,B,H,dh)
    k = k / math.sqrt(dh)
    gates = P.matmul(x_in, p["if_proj"], kind).reshape(b, s, 2, h)
    i_raw, f_raw = (jnp.moveaxis(gates[:, :, j], 1, 0) for j in range(2))

    def step(carry, xs):
        c, n, m = carry
        q_t, k_t, v_t, i_t, f_t = xs
        ig, fg, m = _stabilized_gates(i_t, f_t, m)
        c = fg[..., None, None] * c + ig[..., None, None] * (
            k_t[..., :, None] * v_t[..., None, :])
        n = fg[..., None] * n + ig[..., None] * k_t
        denom = jnp.maximum(jnp.abs(jnp.sum(n * q_t, -1)), jnp.exp(-m))
        out = P.einsum("bhd,bhde->bhe", q_t, c, kind) / denom[..., None]
        return (c, n, m), out

    init = (jnp.zeros((b, h, dh, dh), F32), jnp.zeros((b, h, dh), F32),
            jnp.full((b, h), -1e30, F32))
    _, hs = jax.lax.scan(step, init, (q, k, v, i_raw, f_raw))
    hs = jnp.moveaxis(hs, 0, 1).reshape(b, s, di)
    return P.matmul(hs * jax.nn.silu(gate), p["down"], kind)


def _slstm(p, x, h, kind):
    b, s, d = x.shape
    di = p["down"].shape[0]
    dh = di // h
    up = P.matmul(x, p["up"], kind)
    x_in, gate = up[..., :di], up[..., di:]
    wx = jnp.moveaxis(P.matmul(x_in, p["wx"], kind).reshape(b, s, 4, h, dh),
                      1, 0)                                       # (S,B,4,H,dh)
    r = p["r"]

    def step(carry, wx_t):
        c, n, hid, m = carry
        pre = wx_t + P.einsum("ghde,bhd->bghe", r, hid, kind)
        z = jnp.tanh(pre[:, 0])
        ig, fg, m = _stabilized_gates(pre[:, 1].mean(-1), pre[:, 2].mean(-1), m)
        o = jax.nn.sigmoid(pre[:, 3])
        c = fg[..., None] * c + ig[..., None] * z
        n = fg[..., None] * n + ig[..., None]
        hid = o * c / jnp.maximum(n, 1e-6)
        return (c, n, hid, m), hid

    zeros = jnp.zeros((b, h, dh), F32)
    _, hs = jax.lax.scan(step, (zeros, zeros, zeros, jnp.full((b, h), -1e30, F32)),
                         wx)
    hs = jnp.moveaxis(hs, 0, 1).reshape(b, s, di)
    return P.matmul(hs * jax.nn.silu(gate), p["down"], kind)


def embed(params: dict, cfg: dict, tokens, kind: str = "highest"):
    """tokens (B, S) → mean-pooled final hidden states (B, d), float32."""
    h, eps = cfg["num_heads"], cfg["norm_eps"]
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)

    def m_block(x, lp):
        return x + _mlstm(lp["mixer"], _rms(x, lp["ln"]["scale"], eps), h,
                          kind), None

    def group(x, gp):
        mp, sp = gp
        x, _ = jax.lax.scan(m_block, x, mp)
        return x + _slstm(sp["mixer"], _rms(x, sp["ln"]["scale"], eps), h,
                          kind), None

    if "mlstm_groups" in params:
        x, _ = jax.lax.scan(group, x, (params["mlstm_groups"], params["slstm"]))
    if "mlstm_tail" in params:
        x, _ = jax.lax.scan(m_block, x, params["mlstm_tail"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    return jnp.mean(x, axis=1)
