"""Backbone assembly for all assigned architecture families.

Every family compiles to O(1)-size HLO via lax.scan over (groups of) layers
with stacked parameters; heterogeneous stacks (gemma3 local/global windows,
zamba2 shared-attention cadence, xLSTM mLSTM/sLSTM ratio) are expressed either
as per-layer *traced* metadata (window/theta arrays scanned alongside params)
or as grouped two-level scans, never as per-layer unrolled HLO.

Public entry points (uniform across families):
  init_params(key, cfg)                  → param pytree
  forward(params, cfg, batch)            → final hidden states (B, S, D)
  pool(hidden)                           → (B, D) embedding for the AFL head
  lm_logits(params, cfg, hidden)         → (B, S, vocab)
  init_cache(cfg, batch, max_seq)        → decode cache pytree
  prefill(params, cfg, batch, max_seq)   → (hidden, cache)
  decode_step(params, cfg, tok, cache, pos) → (hidden (B,1,D), cache)

``batch`` is a dict: tokens (B, S) int32 and, for VLM/audio archs, the
modality stub: prefix_embeds (B, P, D) (llava patches, consumed as prefix
tokens) or enc_feats (B, S_enc, D) (seamless audio frames → encoder input).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro import scopes
from repro.config import ModelConfig
from repro.core import act
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models import xlstm as X

Params = Dict[str, Any]


# ------------------------------------------------------------ per-layer meta
def layer_meta(cfg: ModelConfig, n_layers: int):
    """(window, theta) per layer as arrays scanned with the params.

    window==0 encodes "full attention" (sdpa maps <=0 to unbounded).
    """
    idx = np.arange(n_layers)
    if cfg.window and cfg.global_every:
        is_global = (idx % cfg.global_every) == (cfg.global_every - 1)
    elif cfg.window:
        is_global = np.zeros(n_layers, bool)
    else:
        is_global = np.ones(n_layers, bool)
    window = np.where(is_global, 0, cfg.window).astype(np.int32)
    theta_g = cfg.rope_theta_global or cfg.rope_theta
    theta = np.where(is_global, theta_g, cfg.rope_theta).astype(np.float32)
    return jnp.asarray(window), jnp.asarray(theta)


def _attn_dims(cfg: ModelConfig) -> L.AttnDims:
    return L.AttnDims(
        d_model=cfg.d_model,
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.resolved_head_dim,
        qk_norm=cfg.qk_norm,
    )


# ----------------------------------------------------------- dense/moe block
def _init_block(key, cfg: ModelConfig, cross_attn: bool = False):
    ks = jax.random.split(key, 6)
    dt = cfg.param_dtype
    p = {
        "ln1": L.init_norm(cfg.d_model, dt, cfg.norm == "layer"),
        "attn": L.init_attention(ks[0], _attn_dims(cfg), dt),
        "ln2": L.init_norm(cfg.d_model, dt, cfg.norm == "layer"),
    }
    if cfg.moe is not None:
        p["moe"] = M.init_moe(ks[1], cfg.d_model, cfg.d_ff, cfg.moe, cfg.activation, dt)
    elif cfg.d_ff:
        p["mlp"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.activation, dt)
    if cross_attn:
        p["ln_x"] = L.init_norm(cfg.d_model, dt, cfg.norm == "layer")
        p["xattn"] = L.init_attention(ks[2], _attn_dims(cfg), dt)
    return p


@jax.named_scope(scopes.FFN)
def _block_ffn(p, cfg: ModelConfig, x):
    x = act.constrain_bsd(x)
    h = L.norm_apply(p["ln2"], x, cfg.norm_eps, cfg.norm)
    if cfg.moe is not None:
        out, _aux = M.moe_apply(p["moe"], h, cfg.moe, cfg.activation)
    elif cfg.d_ff:
        out = L.mlp_apply(p["mlp"], h, cfg.activation)
    else:
        out = jnp.zeros_like(x)
    return x + out


def _block_fwd(p, cfg: ModelConfig, x, positions, window, theta,
               *, causal=True, kv_cache=None, pos=None, memory_kv=None):
    """One attention block. Returns (x, new_kv or computed kv)."""
    x, new_kv = _block_mixer(p, cfg, x, positions, window, theta,
                             causal=causal, kv_cache=kv_cache, pos=pos,
                             memory_kv=memory_kv)
    return _block_ffn(p, cfg, x), new_kv


@jax.named_scope(scopes.MIXER)
def _block_mixer(p, cfg: ModelConfig, x, positions, window, theta,
                 *, causal, kv_cache, pos, memory_kv):
    """The attention sub-layers of a block: (x, new_kv or computed kv)."""
    dims = _attn_dims(cfg)
    x = act.constrain_bsd(x)
    h = L.norm_apply(p["ln1"], x, cfg.norm_eps, cfg.norm)
    q, k, v = L.qkv_project(p["attn"], dims, h, positions, theta, cfg.norm_eps)
    q = act.constrain_heads(q)
    k = act.constrain_heads(k)
    v = act.constrain_heads(v)
    if kv_cache is None:
        with jax.named_scope(scopes.SEQMIX):
            attn = L.sdpa(q, k, v, causal=causal, window=window,
                          softcap=cfg.logit_softcap)
        new_kv = (k, v)
    else:
        ck, cv = kv_cache
        clen = ck.shape[2]
        # Ring-buffer semantics (§Perf long_500k): when the allocated cache
        # is shorter than the context, slot = pos % clen keeps exactly the
        # last clen positions (keys stored rope'd at absolute positions, so
        # dot products are position-correct). The sliding-window mask is
        # then enforced *by the ring itself* — disable it (a slot-index
        # window mask would wrongly evict wrapped slots) and let causality
        # (slot <= pos) mask the not-yet-written slots while pos < clen.
        slot = jax.lax.rem(jnp.asarray(pos, jnp.int32), jnp.int32(clen))
        win = jnp.asarray(window, jnp.int32)
        win = jnp.where((win > 0) & (clen <= win), 0, win)
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, 0, slot, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, 0, slot, 0))
        with jax.named_scope(scopes.SEQMIX):
            attn = L.sdpa(q, ck, cv, causal=True, window=win, q_offset=pos,
                          softcap=cfg.logit_softcap)
        new_kv = (ck, cv)
    x = x + L.attn_out(p["attn"], attn)
    if memory_kv is not None:  # cross attention (enc-dec)
        hx = L.norm_apply(p["ln_x"], x, cfg.norm_eps, cfg.norm)
        qx, _, _ = L.qkv_project(p["xattn"], dims, hx, positions, None)
        mk, mv = memory_kv
        with jax.named_scope(scopes.SEQMIX):
            xattn = L.sdpa(qx, mk, mv, causal=False, window=None)
        x = x + L.attn_out(p["xattn"], xattn)
    return x, new_kv


def _memory_kv(p, cfg: ModelConfig, memory):
    """Cross-attention K/V from encoder memory (per decoder layer)."""
    dims = _attn_dims(cfg)
    _, mk, mv = L.qkv_project(p["xattn"], dims, memory, None, None)
    return mk, mv


# ------------------------------------------------------------ embedding etc.
def _init_common(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    dt = cfg.param_dtype
    p = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model), jnp.float32)
                  * 0.02).astype(dt),
        "final_norm": L.init_norm(cfg.d_model, dt, cfg.norm == "layer"),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(ks[1], cfg.d_model, cfg.vocab_size, dt)
    if cfg.prefix_tokens:
        p["mm_proj"] = L.dense_init(ks[2], cfg.d_model, cfg.d_model, dt)
    if cfg.encoder_layers:
        p["enc_proj"] = L.dense_init(ks[3], cfg.d_model, cfg.d_model, dt)
    return p


@jax.named_scope(scopes.EMBED)
def embed_inputs(params: Params, cfg: ModelConfig, batch):
    """tokens (+ optional VLM prefix) → (x (B,S,D), positions (B,S))."""
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    if cfg.prefix_tokens:
        prefix = batch["prefix_embeds"].astype(x.dtype) @ params["mm_proj"]
        x = jnp.concatenate([prefix, x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return act.constrain_bsd(x), positions


@jax.named_scope(scopes.POOL)
def pool(hidden: jax.Array) -> jax.Array:
    """Sequence-mean embedding for the AFL analytic head."""
    return jnp.mean(hidden, axis=1)


@jax.named_scope(scopes.FINAL_NORM)
def _final_norm(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    return L.norm_apply(params["final_norm"], x, cfg.norm_eps, cfg.norm)


def lm_logits(params: Params, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    table = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return hidden @ table


# =====================================================================
# family: dense / moe (uniform stack, single scan)
# =====================================================================
def _init_dense(key, cfg: ModelConfig):
    p = _init_common(key, cfg)
    keys = jax.random.split(jax.random.fold_in(key, 1), cfg.num_layers)
    p["layers"] = jax.vmap(lambda k: _init_block(k, cfg))(keys)
    return p


def _dense_forward(params, cfg, x, positions, causal=True):
    window, theta = layer_meta(cfg, cfg.num_layers)

    def body(h, xs):
        lp, w, th = xs
        h, _ = _block_fwd(lp, cfg, h, positions, w, th, causal=causal)
        return h, None

    x, _ = jax.lax.scan(body, x, (params["layers"], window, theta))
    return _final_norm(params, cfg, x)


def _dense_prefill(params, cfg, x, positions, max_seq):
    window, theta = layer_meta(cfg, cfg.num_layers)
    b, s, _ = x.shape
    hk, hd = cfg.num_kv_heads, cfg.resolved_head_dim

    def body(h, xs):
        lp, w, th = xs
        h, (k, v) = _block_fwd(lp, cfg, h, positions, w, th)
        pad = max_seq - s
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        return h, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], window, theta))
    cache = {"k": ks, "v": vs}  # (L, B, Hk, max_seq, hd)
    return _final_norm(params, cfg, x), cache


def _dense_decode(params, cfg, x, cache, pos):
    """One-token decode, cache as fori_loop carry (§Perf decode iteration).

    Threading the cache through scan *ys* rewrites every layer's full cache
    slice per token (~2× cache bytes/step); carrying the stacked cache and
    dynamic-update-slicing ONE token at (layer, ·, ·, slot, ·) leaves the
    write O(1) and the read just the layer's K/V (needed by attention anyway).
    Ring semantics as in _block_fwd: slot = pos % cache_len.
    """
    window, theta = layer_meta(cfg, cfg.num_layers)
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, jnp.int32)
    dims = _attn_dims(cfg)
    clen = cache["k"].shape[3]
    slot = jax.lax.rem(jnp.asarray(pos, jnp.int32), jnp.int32(clen))

    def body(i, carry):
        h, ck_all, cv_all = carry
        lp = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            params["layers"])
        w, th = window[i], theta[i]
        hn = L.norm_apply(lp["ln1"], act.constrain_bsd(h), cfg.norm_eps, cfg.norm)
        q, k, v = L.qkv_project(lp["attn"], dims, hn, positions, th,
                                cfg.norm_eps)
        ck_all = jax.lax.dynamic_update_slice(
            ck_all, k[None].astype(ck_all.dtype), (i, 0, 0, slot, 0))
        cv_all = jax.lax.dynamic_update_slice(
            cv_all, v[None].astype(cv_all.dtype), (i, 0, 0, slot, 0))
        ck = jax.lax.dynamic_index_in_dim(ck_all, i, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(cv_all, i, 0, keepdims=False)
        win = jnp.where((w > 0) & (clen <= w), 0, w)
        attn = L.sdpa(q, ck, cv, causal=True, window=win, q_offset=pos,
                      softcap=cfg.logit_softcap)
        h = h + L.attn_out(lp["attn"], attn)
        h = _block_ffn(lp, cfg, h)
        return h, ck_all, cv_all

    x, ks, vs = jax.lax.fori_loop(
        0, cfg.num_layers, body, (x, cache["k"], cache["v"]))
    x = _final_norm(params, cfg, x)
    return x, {"k": ks, "v": vs}


def _dense_cache(cfg, batch, max_seq, dtype):
    hk, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (cfg.num_layers, batch, hk, max_seq, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# =====================================================================
# family: hybrid (zamba2) — groups of (G-1 mamba + 1 shared attn) + tail
# =====================================================================
def _hybrid_split(cfg: ModelConfig):
    g = cfg.shared_attn_every
    n_groups = cfg.num_layers // g
    tail = cfg.num_layers - n_groups * g
    return g, n_groups, tail


def _init_mamba_layer(key, cfg: ModelConfig):
    dt = cfg.param_dtype
    return {
        "ln": L.init_norm(cfg.d_model, dt),
        "mixer": S.init_mamba(key, cfg.d_model, cfg.ssm, dt),
    }


def _init_hybrid(key, cfg: ModelConfig):
    p = _init_common(key, cfg)
    g, n_groups, tail = _hybrid_split(cfg)
    kg, kt, ka = jax.random.split(jax.random.fold_in(key, 2), 3)
    if n_groups:
        keys = jax.random.split(kg, (n_groups, g - 1))
        p["mamba_groups"] = jax.vmap(jax.vmap(
            lambda k: _init_mamba_layer(k, cfg)))(keys)
    if tail:
        keys_t = jax.random.split(kt, tail)
        p["mamba_tail"] = jax.vmap(lambda k: _init_mamba_layer(k, cfg))(keys_t)
    p["shared_attn"] = _init_block(ka, cfg)
    return p


def _mamba_layer_fwd(lp, cfg, h, state=None):
    h = act.constrain_bsd(h)
    hin = L.norm_apply(lp["ln"], h, cfg.norm_eps, cfg.norm)
    if state is None:
        return h + S.mamba_apply(lp["mixer"], hin, cfg.ssm), None
    out, new_state = (
        S.mamba_decode(lp["mixer"], hin, state, cfg.ssm)
        if hin.shape[1] == 1
        else S.mamba_apply(lp["mixer"], hin, cfg.ssm,
                           init_state=state, return_state=True)
    )
    return h + out, new_state


def _hybrid_forward(params, cfg, x, positions):
    g, n_groups, tail = _hybrid_split(cfg)

    def mamba_body(h, lp):
        h, _ = _mamba_layer_fwd(lp, cfg, h)
        return h, None

    def group_body(h, gp):
        h, _ = jax.lax.scan(mamba_body, h, gp)
        h, _ = _block_fwd(params["shared_attn"], cfg, h, positions,
                          cfg.window or 0, cfg.rope_theta)
        return h, None

    if n_groups:
        x, _ = jax.lax.scan(group_body, x, params["mamba_groups"])
    if tail:
        x, _ = jax.lax.scan(mamba_body, x, params["mamba_tail"])
    return _final_norm(params, cfg, x)


def _hybrid_cache(cfg, batch, max_seq, dtype):
    g, n_groups, tail = _hybrid_split(cfg)
    hk, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    one = S.init_mamba_state(batch, cfg.d_model, cfg.ssm, dtype)
    cache = {}
    if n_groups:
        cache["mamba_groups"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_groups, g - 1) + a.shape).copy(), one
        )
        cache["attn"] = {
            "k": jnp.zeros((n_groups, batch, hk, max_seq, hd), dtype),
            "v": jnp.zeros((n_groups, batch, hk, max_seq, hd), dtype),
        }
    if tail:
        cache["mamba_tail"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (tail,) + a.shape).copy(), one
        )
    return cache


def _hybrid_step(params, cfg, x, positions, cache, pos, max_seq):
    """Shared path for prefill (S>1) and decode (S=1) with state carry."""
    g, n_groups, tail = _hybrid_split(cfg)
    s = x.shape[1]

    def mamba_body(h, xs):
        lp, st = xs
        h, new_st = _mamba_layer_fwd(lp, cfg, h, state=st)
        return h, new_st

    new_cache = dict(cache)
    if n_groups:
        def group_body(h, xs):
            gp, gst, ck, cv = xs
            h, new_gst = jax.lax.scan(mamba_body, h, (gp, gst))
            if s > 1:  # prefill: write kv at [0, s)
                h, (k, v) = _block_fwd(params["shared_attn"], cfg, h, positions,
                                       cfg.window or 0, cfg.rope_theta)
                pad = max_seq - s
                nk = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))).astype(ck.dtype)
                nv = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))).astype(cv.dtype)
            else:
                h, (nk, nv) = _block_fwd(params["shared_attn"], cfg, h, positions,
                                         cfg.window or 0, cfg.rope_theta,
                                         kv_cache=(ck, cv), pos=pos)
            return h, (new_gst, nk, nv)

        x, (gst, ks, vs) = jax.lax.scan(
            group_body, x,
            (params["mamba_groups"], cache["mamba_groups"],
             cache["attn"]["k"], cache["attn"]["v"]),
        )
        new_cache["mamba_groups"] = gst
        new_cache["attn"] = {"k": ks, "v": vs}
    if tail:
        x, tst = jax.lax.scan(mamba_body, x, (params["mamba_tail"], cache["mamba_tail"]))
        new_cache["mamba_tail"] = tst
    return _final_norm(params, cfg, x), new_cache


# =====================================================================
# family: xlstm — groups of (N-1 mLSTM + 1 sLSTM)
# =====================================================================
def _xlstm_split(cfg: ModelConfig):
    g = cfg.slstm_every
    n_groups = cfg.num_layers // g
    tail = cfg.num_layers - n_groups * g
    return g, n_groups, tail


def _init_xlstm(key, cfg: ModelConfig):
    p = _init_common(key, cfg)
    g, n_groups, tail = _xlstm_split(cfg)
    dt = cfg.param_dtype
    km, ks_, kt = jax.random.split(jax.random.fold_in(key, 3), 3)

    def init_m(k):
        return {"ln": L.init_norm(cfg.d_model, dt),
                "mixer": X.init_mlstm(k, cfg.d_model, cfg.num_heads, dt)}

    def init_s(k):
        return {"ln": L.init_norm(cfg.d_model, dt),
                "mixer": X.init_slstm(k, cfg.d_model, cfg.num_heads, dt)}

    if n_groups:
        keys = jax.random.split(km, (n_groups, g - 1))
        p["mlstm_groups"] = jax.vmap(jax.vmap(init_m))(keys)
        p["slstm"] = jax.vmap(init_s)(jax.random.split(ks_, n_groups))
    if tail:
        p["mlstm_tail"] = jax.vmap(init_m)(jax.random.split(kt, tail))
    return p


def _xlstm_run(params, cfg, x, states=None):
    """states=None → plain forward; else threads and returns states."""
    g, n_groups, tail = _xlstm_split(cfg)
    want_state = states is not None

    @jax.named_scope(scopes.MIXER)
    def mixer(apply, lp, h, st):
        hin = L.norm_apply(lp["ln"], h, cfg.norm_eps, cfg.norm)
        if want_state:
            out, nst = apply(lp["mixer"], hin, cfg.num_heads,
                             init_state=st, return_state=True)
            return h + out, nst
        return h + apply(lp["mixer"], hin, cfg.num_heads), None

    def m_body(h, xs):
        lp, st = xs if want_state else (xs, None)
        return mixer(X.mlstm_apply, lp, act.constrain_bsd(h), st)

    def group_body(h, xs):
        if want_state:
            gp, sp, gst, sst = xs
            h, new_gst = jax.lax.scan(m_body, h, (gp, gst))
            h, new_sst = mixer(X.slstm_apply, sp, h, sst)
            return h, (new_gst, new_sst)
        gp, sp = xs
        h, _ = jax.lax.scan(m_body, h, gp)
        return mixer(X.slstm_apply, sp, h, None)

    new_states: dict = {} if want_state else None
    if n_groups:
        if want_state:
            x, (gst, sst) = jax.lax.scan(
                group_body, x,
                (params["mlstm_groups"], params["slstm"],
                 states["mlstm_groups"], states["slstm"]),
            )
            new_states["mlstm_groups"], new_states["slstm"] = gst, sst
        else:
            x, _ = jax.lax.scan(group_body, x, (params["mlstm_groups"], params["slstm"]))
    if tail:
        if want_state:
            x, tst = jax.lax.scan(m_body, x, (params["mlstm_tail"], states["mlstm_tail"]))
            new_states["mlstm_tail"] = tst
        else:
            x, _ = jax.lax.scan(m_body, x, params["mlstm_tail"])
    x = _final_norm(params, cfg, x)
    return (x, new_states) if want_state else x


def _xlstm_cache(cfg, batch, max_seq, dtype):
    g, n_groups, tail = _xlstm_split(cfg)
    m_one = X.init_mlstm_state(batch, cfg.d_model, cfg.num_heads)
    s_one = X.init_slstm_state(batch, cfg.d_model, cfg.num_heads)
    cache = {}
    tile = lambda tree, dims: jax.tree.map(
        lambda a: jnp.broadcast_to(a, dims + a.shape).copy(), tree)
    if n_groups:
        cache["mlstm_groups"] = tile(m_one, (n_groups, g - 1))
        cache["slstm"] = tile(s_one, (n_groups,))
    if tail:
        cache["mlstm_tail"] = tile(m_one, (tail,))
    return cache


# =====================================================================
# family: encdec (seamless) — encoder + cross-attending decoder
# =====================================================================
def _init_encdec(key, cfg: ModelConfig):
    p = _init_common(key, cfg)
    ke, kd = jax.random.split(jax.random.fold_in(key, 4))
    enc_keys = jax.random.split(ke, cfg.encoder_layers)
    p["enc_layers"] = jax.vmap(lambda k: _init_block(k, cfg))(enc_keys)
    p["enc_norm"] = L.init_norm(cfg.d_model, cfg.param_dtype, cfg.norm == "layer")
    dec_keys = jax.random.split(kd, cfg.num_layers)
    p["layers"] = jax.vmap(lambda k: _init_block(k, cfg, cross_attn=True))(dec_keys)
    return p


def _encode(params, cfg, enc_feats):
    x = enc_feats.astype(cfg.param_dtype) @ params["enc_proj"]
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def body(h, lp):
        h, _ = _block_fwd(lp, cfg, h, positions, 0, cfg.rope_theta, causal=False)
        return h, None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return L.norm_apply(params["enc_norm"], x, cfg.norm_eps, cfg.norm)


def _encdec_forward(params, cfg, batch):
    memory = _encode(params, cfg, batch["enc_feats"])
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def body(h, lp):
        mkv = _memory_kv(lp, cfg, memory)
        h, _ = _block_fwd(lp, cfg, h, positions, 0, cfg.rope_theta, memory_kv=mkv)
        return h, None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return _final_norm(params, cfg, x)


def _encdec_cache(cfg, batch, max_seq, dtype):
    hk, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    self_shape = (cfg.num_layers, batch, hk, max_seq, hd)
    cross_shape = (cfg.num_layers, batch, hk, cfg.encoder_seq, hd)
    return {
        "k": jnp.zeros(self_shape, dtype),
        "v": jnp.zeros(self_shape, dtype),
        "xk": jnp.zeros(cross_shape, dtype),
        "xv": jnp.zeros(cross_shape, dtype),
    }


def _encdec_prefill(params, cfg, batch, max_seq):
    memory = _encode(params, cfg, batch["enc_feats"])
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def body(h, lp):
        mkv = _memory_kv(lp, cfg, memory)
        h, (k, v) = _block_fwd(lp, cfg, h, positions, 0, cfg.rope_theta,
                               memory_kv=mkv)
        pad = max_seq - s
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        return h, (k, v, mkv[0], mkv[1])

    x, (ks, vs, xks, xvs) = jax.lax.scan(body, x, params["layers"])
    cache = {"k": ks, "v": vs, "xk": xks, "xv": xvs}
    return _final_norm(params, cfg, x), cache


def _encdec_decode(params, cfg, x, cache, pos):
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, jnp.int32)

    def body(h, xs):
        lp, ck, cv, xk, xv = xs
        h, (nk, nv) = _block_fwd(lp, cfg, h, positions, 0, cfg.rope_theta,
                                 kv_cache=(ck, cv), pos=pos, memory_kv=(xk, xv))
        return h, (nk, nv)

    x, (ks, vs) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"], cache["xk"], cache["xv"])
    )
    x = _final_norm(params, cfg, x)
    return x, {"k": ks, "v": vs, "xk": cache["xk"], "xv": cache["xv"]}


# =====================================================================
# public dispatch
# =====================================================================
def init_params(key, cfg: ModelConfig) -> Params:
    fam = cfg.arch_type
    if fam in ("dense", "moe"):
        return _init_dense(key, cfg)
    if fam == "hybrid":
        return _init_hybrid(key, cfg)
    if fam == "xlstm":
        return _init_xlstm(key, cfg)
    if fam == "encdec":
        return _init_encdec(key, cfg)
    raise ValueError(f"unknown arch_type {fam!r}")


def forward(params: Params, cfg: ModelConfig, batch) -> jax.Array:
    fam = cfg.arch_type
    if fam == "encdec":
        return _encdec_forward(params, cfg, batch)
    x, positions = embed_inputs(params, cfg, batch)
    if fam in ("dense", "moe"):
        return _dense_forward(params, cfg, x, positions)
    if fam == "hybrid":
        return _hybrid_forward(params, cfg, x, positions)
    if fam == "xlstm":
        return _xlstm_run(params, cfg, x)
    raise ValueError(fam)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None) -> Any:
    dtype = dtype or cfg.param_dtype
    fam = cfg.arch_type
    if fam in ("dense", "moe"):
        return _dense_cache(cfg, batch, max_seq, dtype)
    if fam == "hybrid":
        return _hybrid_cache(cfg, batch, max_seq, dtype)
    if fam == "xlstm":
        return _xlstm_cache(cfg, batch, max_seq, dtype)
    if fam == "encdec":
        return _encdec_cache(cfg, batch, max_seq, dtype)
    raise ValueError(fam)


def prefill(params: Params, cfg: ModelConfig, batch, max_seq: int):
    fam = cfg.arch_type
    if fam == "encdec":
        return _encdec_prefill(params, cfg, batch, max_seq)
    x, positions = embed_inputs(params, cfg, batch)
    if fam in ("dense", "moe"):
        return _dense_prefill(params, cfg, x, positions, max_seq)
    if fam == "hybrid":
        cache = _hybrid_cache(cfg, x.shape[0], max_seq, cfg.param_dtype)
        return _hybrid_step(params, cfg, x, positions, cache, None, max_seq)
    if fam == "xlstm":
        cache = _xlstm_cache(cfg, x.shape[0], max_seq, cfg.param_dtype)
        return _xlstm_run(params, cfg, x, states=cache)
    raise ValueError(fam)


def decode_step(params: Params, cfg: ModelConfig, token, cache, pos):
    """token: (B,) int32; pos: traced scalar position. → ((B,1,D), cache)."""
    x = jnp.take(params["embed"], token[:, None], axis=0)
    fam = cfg.arch_type
    if fam in ("dense", "moe"):
        return _dense_decode(params, cfg, x, cache, pos)
    if fam == "hybrid":
        b = x.shape[0]
        positions = jnp.full((b, 1), pos, jnp.int32)
        return _hybrid_step(params, cfg, x, positions, cache, pos,
                            cache["attn"]["k"].shape[3] if "attn" in cache else 0)
    if fam == "xlstm":
        return _xlstm_run(params, cfg, x, states=cache)
    if fam == "encdec":
        return _encdec_decode(params, cfg, x, cache, pos)
    raise ValueError(fam)
