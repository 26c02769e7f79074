"""Plain reference of a dense decoder-only transformer as the program builds
it (MiniCPM, arXiv:2404.06395): pre-norm blocks of causal multi-head
attention with rotary positions and a SwiGLU MLP, a final RMS norm, mean
pooling.

Departures from the MiniCPM paper, as in the program: no embedding scale
(``scale_emb``), no depth-scaled residual branches (``scale_depth``), and
rotary embedding by halves (``x₁ cos − x₂ sin``, ``x₂ cos + x₁ sin``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import precision as P

F32 = jnp.float32


def init(cfg: dict, key) -> dict:
    """Weights from ``key``: normal(0, 1/fan_in) projections, normal(0, 0.02)
    embeddings, unit norm scales, in the configuration's dtype. The input
    embedding is tied to the output head, which the pooled embedding never
    reads, so no head is made."""
    d, L, f = cfg["d_model"], cfg["num_layers"], cfg["d_ff"]
    h, hk = cfg["num_heads"], cfg["num_kv_heads"]
    hd = cfg.get("head_dim") or d // h
    dt = jnp.dtype(cfg["dtype"])
    counter = iter(range(1 << 20))

    def normal(shape, scale):
        k = jax.random.fold_in(key, next(counter))
        return (jax.random.normal(k, shape, F32) * scale).astype(dt)

    ones = lambda: {"scale": jnp.ones((L, d), dt)}
    layers = {
        "ln1": ones(),
        "attn": {"wq": normal((L, d, h * hd), d ** -0.5),
                 "wk": normal((L, d, hk * hd), d ** -0.5),
                 "wv": normal((L, d, hk * hd), d ** -0.5),
                 "wo": normal((L, h * hd, d), (h * hd) ** -0.5)},
        "ln2": ones(),
        "mlp": {"w_up": normal((L, d, f), d ** -0.5),
                "w_down": normal((L, f, d), f ** -0.5),
                "w_gate": normal((L, d, f), d ** -0.5)},
    }
    return {"embed": normal((cfg["vocab_size"], d), 0.02),
            "final_norm": {"scale": jnp.ones((d,), dt)},
            "layers": layers}


def forward_ops(cfg: dict, seq: int) -> float:
    """Operations of the forward for one sequence of ``seq`` tokens: the
    projections and the MLP of every layer, and the attention core over the
    whole S × S square (the embedding lookup, norms and pooling not
    counted)."""
    d, L, f = cfg["d_model"], cfg["num_layers"], cfg["d_ff"]
    h, hk = cfg["num_heads"], cfg["num_kv_heads"]
    hd = cfg.get("head_dim") or d // h
    attn_params = d * h * hd + 2 * d * hk * hd + h * hd * d
    mlp_params = (3 if cfg.get("activation", "swiglu") == "swiglu" else 2) * d * f
    matmuls = 2 * L * (attn_params + mlp_params) * seq
    # scores QKᵀ and the weighted sum PV over the whole S×S square
    attention = 4 * L * seq * seq * h * hd
    return matmuls + attention


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, theta):
    """x (B, H, S, hd), positions 0..S−1."""
    s, hd = x.shape[2], x.shape[3]
    half = hd // 2
    inv_freq = theta ** -(jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def embed(params: dict, cfg: dict, tokens, kind: str = "highest"):
    """tokens (B, S) → mean-pooled final hidden states (B, d), float32."""
    d, h, hk = cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"]
    hd = cfg.get("head_dim") or d // h
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def heads(y, n):
        return y.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

    def block(x, lp):
        a = lp["attn"]
        y = _rms(x, lp["ln1"]["scale"], eps)
        q = _rope(heads(P.matmul(y, a["wq"], kind), h), theta)
        k = _rope(heads(P.matmul(y, a["wk"], kind), hk), theta)
        v = heads(P.matmul(y, a["wv"], kind), hk)
        q = q.reshape(b, hk, h // hk, s, hd)
        logits = P.einsum("bkgqd,bkpd->bkgqp", q, k, kind) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), -1)
        ctx = P.einsum("bkgqp,bkpd->bkgqd", probs, v, kind)
        ctx = ctx.reshape(b, h, s, hd).transpose(0, 2, 1, 3).reshape(b, s, h * hd)
        x = x + P.matmul(ctx, a["wo"], kind)
        m = lp["mlp"]
        y = _rms(x, lp["ln2"]["scale"], eps)
        up = jax.nn.silu(P.matmul(y, m["w_gate"], kind)) * P.matmul(y, m["w_up"], kind)
        return x + P.matmul(up, m["w_down"], kind), None

    x, _ = jax.lax.scan(block, x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    return jnp.mean(x, axis=1)
