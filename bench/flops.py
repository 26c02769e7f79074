"""Operations and bytes from shapes, for the utilization and roofline
metrics. Counted from the configuration's own sizes, never from the
program's compiled cost analysis, so a change to the program cannot move
the yardstick. One multiply-add counts as two operations.
"""

from __future__ import annotations


def _xlstm_per_token(cfg: dict) -> float:
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
    return (2 * (n_m * m_params + n_s * s_params)
            + h * (n_m * m_memory + n_s * s_recurrent))


def _dense_per_sample(cfg: dict, seq: int) -> float:
    d, L, f = cfg["d_model"], cfg["num_layers"], cfg["d_ff"]
    h, hk = cfg["num_heads"], cfg["num_kv_heads"]
    hd = cfg.get("head_dim") or d // h
    attn_params = d * h * hd + 2 * d * hk * hd + h * hd * d
    mlp_params = (3 if cfg.get("activation", "swiglu") == "swiglu" else 2) * d * f
    matmuls = 2 * L * (attn_params + mlp_params) * seq
    # scores QKᵀ and the weighted sum PV over the whole S×S square
    attention = 4 * L * seq * seq * h * hd
    return matmuls + attention


def forward_per_sample(cfg: dict, seq: int) -> float:
    """Operations of the backbone forward for one sequence of ``seq`` tokens
    (the embedding lookup, norms and pooling are not counted)."""
    if cfg["family"] == "xlstm":
        return _xlstm_per_token(cfg) * seq
    if cfg["family"] == "dense":
        return _dense_per_sample(cfg, seq)
    raise ValueError(f"no operation count for family {cfg['family']!r}")


def fold_per_sample(d: int, c: int) -> float:
    """One row folded into XᵀX (d × d) and XᵀY (d × c)."""
    return 2 * d * (d + c)


def local_per_sample(cfg: dict, seq: int) -> float:
    """The local stage's model operations per sample: forward and fold."""
    return (forward_per_sample(cfg, seq)
            + fold_per_sample(cfg["d_model"], cfg["num_classes"]))


def gram_kernel(n: int, d: int, c: int, itemsize: int = 4):
    """(operations, bytes) of one fused Gram update of n rows: the products
    XᵀX and XᵀY, reading X and Y once and writing G and Q once."""
    ops = 2 * n * d * d + 2 * n * d * c
    nbytes = itemsize * (n * d + n * c + d * d + d * c)
    return float(ops), float(nbytes)


def roofline_seconds(ops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute and the
    memory bound."""
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
