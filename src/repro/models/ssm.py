"""State-space & recurrent mixers: Mamba (Jamba), mLSTM / sLSTM (xLSTM).

TPU adaptation notes (DESIGN.md §3): the CUDA selective-scan kernel is
re-thought, not ported —

  - Mamba runs as a *chunked* scan: jax.lax.scan over sequence chunks
    carrying the (d_inner, d_state) SSM state, with an intra-chunk
    associative scan (log₂ depth on the VPU). The (B, S, d, n) expanded
    state is never materialized: chunk inputs are Δ/B/C/x slices and the
    C·h contraction happens inside the chunk, so peak memory is O(chunk).
  - mLSTM uses the chunkwise-parallel linear-attention form with running
    max-stabilizers (exp-gates never overflow); intra-chunk work is (L, L)
    matmuls that feed the MXU, inter-chunk state is (nh, dh, dh).
  - sLSTM is inherently sequential (h_{t-1} feeds the gate projections);
    it runs as a remat'd nested scan (outer chunks, inner steps).

All in/x/dt/out/gate projections are quant-units; the recurrence itself
stays fp32 ("all other data full precision", paper §3.4.1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.models.common import init_qdense, qproj

MAMBA_CHUNK = 128
MLSTM_CHUNK = 128
SLSTM_CHUNK = 128


# ------------------------------------------------------------------- Mamba
def init_mamba(key, cfg) -> dict:
    d, di, ds, dc = cfg.d_model, cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    dtr = cfg.mamba_dt_rank
    ks = jax.random.split(key, 6)
    a_init = jnp.tile(jnp.arange(1, ds + 1, dtype=jnp.float32)[None, :], (di, 1))
    return {
        "in": init_qdense(ks[0], d, 2 * di, cfg.param_dtype),
        "conv": jax.random.normal(ks[1], (dc, di), cfg.param_dtype) * (dc ** -0.5),
        "conv_b": jnp.zeros((di,), cfg.param_dtype),
        "x": init_qdense(ks[2], di, dtr + 2 * ds, cfg.param_dtype),
        "dt": init_qdense(ks[3], dtr, di, cfg.param_dtype),
        "dt_bias": jnp.zeros((di,), jnp.float32),
        "A_log": jnp.log(a_init),
        "D": jnp.ones((di,), jnp.float32),
        "out": init_qdense(ks[4], di, d, cfg.param_dtype),
    }


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv. x: (B, S, d); w: (dc, d); state: (B, dc-1, d)."""
    dc = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], dc - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None, :]
              for i in range(dc))
    new_state = xp[:, -(dc - 1):, :] if dc > 1 else None
    return out + b[None, None, :], new_state


def _ssm_combine(lt, rt):
    al, bl = lt
    ar, br = rt
    return al * ar, bl * ar + br


def mamba_apply(p, x, bits, cfg, mode: str, state):
    """x: (B, S, d). bits: {'mamba_in','mamba_x','mamba_dt','mamba_out'}.
    state (decode): {'conv': (B, dc-1, di), 'ssm': (B, di, ds)}."""
    b, s, d = x.shape
    di, ds = cfg.mamba_d_inner, cfg.mamba_d_state
    dtr = cfg.mamba_dt_rank

    xu = qproj(x, p["in"], bits["mamba_in"])
    xm, z = xu[..., :di], xu[..., di:]

    conv_state = state["conv"] if mode == "decode" else None
    xm, new_conv = _causal_conv(xm, p["conv"], p["conv_b"], conv_state)
    xm = jax.nn.silu(xm)

    xdbc = qproj(xm, p["x"], bits["mamba_x"])
    dt_in = xdbc[..., :dtr]
    b_t = xdbc[..., dtr:dtr + ds].astype(jnp.float32)          # (B,S,ds)
    c_t = xdbc[..., dtr + ds:].astype(jnp.float32)             # (B,S,ds)
    delta = jax.nn.softplus(
        qproj(dt_in, p["dt"], bits["mamba_dt"]).astype(jnp.float32)
        + p["dt_bias"][None, None, :])                         # (B,S,di)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))               # (di,ds)
    xf = xm.astype(jnp.float32)

    if mode == "decode":
        # Single-step recurrence.
        da = jnp.exp(delta[:, 0, :, None] * a[None])           # (B,di,ds)
        db = delta[:, 0, :, None] * b_t[:, 0, None, :]         # (B,di,ds)
        h = da * state["ssm"] + db * xf[:, 0, :, None]
        y = jnp.einsum("bdn,bn->bd", h, c_t[:, 0])[:, None, :] \
            + p["D"][None, None, :] * xf
        new_state = {"conv": new_conv, "ssm": h}
    else:
        chunk = min(MAMBA_CHUNK, s)
        assert s % chunk == 0
        nc = s // chunk

        def chunk_step(h_in, inp):
            dl, bl, cl, xl = inp                               # (B,L,·)
            ac = jnp.exp(dl[..., None] * a[None, None])        # (B,L,di,ds)
            bc = (dl * xl)[..., None] * bl[:, :, None, :]      # (B,L,di,ds)
            pc, hc = jax.lax.associative_scan(_ssm_combine, (ac, bc), axis=1)
            h = hc + pc * h_in[:, None]
            y = jnp.einsum("bldn,bln->bld", h, cl)
            return h[:, -1], y

        xs = tuple(
            v.reshape(b, nc, chunk, -1).transpose(1, 0, 2, 3)
            for v in (delta, b_t, c_t, xf))
        h0 = jnp.zeros((b, di, ds), jnp.float32)
        h_last, ys = jax.lax.scan(jax.checkpoint(chunk_step), h0, xs)
        y = ys.transpose(1, 0, 2, 3).reshape(b, s, di) \
            + p["D"][None, None, :] * xf
        new_state = {"conv": jnp.zeros((b, cfg.mamba_d_conv - 1, di),
                                       cfg.param_dtype) if new_conv is None
                     else new_conv.astype(cfg.param_dtype),
                     "ssm": h_last} if mode == "prefill" else None

    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    return qproj(y, p["out"], bits["mamba_out"]), new_state


def init_mamba_state(cfg, batch: int) -> dict:
    return {
        "conv": jnp.zeros((batch, cfg.mamba_d_conv - 1, cfg.mamba_d_inner),
                          cfg.param_dtype),
        "ssm": jnp.zeros((batch, cfg.mamba_d_inner, cfg.mamba_d_state),
                         jnp.float32),
    }


# ------------------------------------------------------------------- mLSTM
def init_mlstm(key, cfg) -> dict:
    d, di = cfg.d_model, cfg.xlstm_d_inner
    nh = cfg.n_heads
    ks = jax.random.split(key, 6)
    return {
        "up": init_qdense(ks[0], d, 2 * di, cfg.param_dtype),
        "wq": init_qdense(ks[1], di, di, cfg.param_dtype),
        "wk": init_qdense(ks[2], di, di, cfg.param_dtype),
        "wv": init_qdense(ks[3], di, di, cfg.param_dtype),
        "wif": init_qdense(ks[4], di, 2 * nh, cfg.param_dtype),
        "down": init_qdense(ks[5], di, d, cfg.param_dtype),
    }


def _mlstm_chunk(carry, inp, nh, dh):
    """One chunkwise-parallel mLSTM step. carry: (C̃ (B,nh,dh,dh),
    ñ (B,nh,dh), m (B,nh)); inp: q,k,v (B,L,nh,dh), i,logf (B,L,nh)."""
    c_in, n_in, m_in = carry
    q, k, v, ig, logf = inp
    b_, l, _, _ = q.shape
    bcum = jnp.cumsum(logf, axis=1)                            # (B,L,nh)
    g = bcum[:, -1]                                            # (B,nh)

    # Intra-chunk decay matrix exponents: Ã[t,s] = b_t - b_s + i_s (s<=t).
    at = bcum.transpose(0, 2, 1)                               # (B,nh,L)
    a_mat = at[:, :, :, None] - at[:, :, None, :] \
        + ig.transpose(0, 2, 1)[:, :, None, :]                 # (B,nh,L,L)
    mask = jnp.tril(jnp.ones((l, l), bool))
    a_mat = jnp.where(mask[None, None], a_mat, -jnp.inf)
    m_intra = jnp.max(a_mat, axis=-1)                          # (B,nh,L)
    m_inter = at + m_in[:, :, None]                            # (B,nh,L)
    m_t = jnp.maximum(m_intra, m_inter)                        # (B,nh,L)

    qf = q.astype(jnp.float32).transpose(0, 2, 1, 3) * (dh ** -0.5)
    kf = k.astype(jnp.float32).transpose(0, 2, 1, 3)
    vf = v.astype(jnp.float32).transpose(0, 2, 1, 3)
    s_qk = jnp.einsum("bhtd,bhsd->bhts", qf, kf)
    w = jnp.where(mask[None, None],
                  jnp.exp(a_mat - m_t[..., None]), 0.0) * s_qk
    inter_coef = jnp.exp(m_inter - m_t)                        # (B,nh,L)
    y_num = jnp.einsum("bhts,bhsd->bhtd", w, vf) \
        + inter_coef[..., None] * jnp.einsum("bhtd,bhde->bhte", qf, c_in)
    row = jnp.sum(w, axis=-1) \
        + inter_coef * jnp.einsum("bhtd,bhd->bht", qf, n_in)
    denom = jnp.maximum(jnp.abs(row), jnp.exp(-m_t))[..., None]
    h = (y_num / denom).transpose(0, 2, 1, 3)                  # (B,L,nh,dh)

    # State update.
    dec = g[:, :, None] - at + ig.transpose(0, 2, 1)           # (B,nh,L)
    m_out = jnp.maximum(m_in + g, jnp.max(dec, axis=-1))
    sc = jnp.exp(dec - m_out[:, :, None])                      # (B,nh,L)
    c_out = jnp.exp(m_in + g - m_out)[:, :, None, None] * c_in \
        + jnp.einsum("bhs,bhsd,bhse->bhde", sc, kf, vf)
    n_out = jnp.exp(m_in + g - m_out)[:, :, None] * n_in \
        + jnp.einsum("bhs,bhsd->bhd", sc, kf)
    return (c_out, n_out, m_out), h


def mlstm_apply(p, x, bits, cfg, mode: str, state):
    """x: (B, S, d). bits: {'lstm_up','lstm_qkv','lstm_if','lstm_down'}."""
    b, s, d = x.shape
    di, nh = cfg.xlstm_d_inner, cfg.n_heads
    dh = di // nh

    up = qproj(x, p["up"], bits["lstm_up"])
    xm, z = up[..., :di], up[..., di:]
    q = qproj(xm, p["wq"], bits["lstm_qkv"]).reshape(b, s, nh, dh)
    k = qproj(xm, p["wk"], bits["lstm_qkv"]).reshape(b, s, nh, dh)
    v = qproj(xm, p["wv"], bits["lstm_qkv"]).reshape(b, s, nh, dh)
    gif = qproj(xm, p["wif"], bits["lstm_if"]).astype(jnp.float32)
    ig, fg = gif[..., :nh], gif[..., nh:]
    logf = jax.nn.log_sigmoid(fg)

    if mode == "decode":
        c_in, n_in, m_in = state["C"], state["n"], state["m"]
        m_t = jnp.maximum(logf[:, 0] + m_in, ig[:, 0])          # (B,nh)
        fp = jnp.exp(logf[:, 0] + m_in - m_t)
        ip = jnp.exp(ig[:, 0] - m_t)
        qf = q[:, 0].astype(jnp.float32) * (dh ** -0.5)         # (B,nh,dh)
        kf = k[:, 0].astype(jnp.float32)
        vf = v[:, 0].astype(jnp.float32)
        c_new = fp[:, :, None, None] * c_in \
            + ip[:, :, None, None] * jnp.einsum("bhd,bhe->bhde", kf, vf)
        n_new = fp[:, :, None] * n_in + ip[:, :, None] * kf
        num = jnp.einsum("bhd,bhde->bhe", qf, c_new)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", qf, n_new)),
                          jnp.exp(-m_t))[..., None]
        h = (num / den).reshape(b, 1, di)
        new_state = {"C": c_new, "n": n_new, "m": m_t}
    else:
        chunk = min(MLSTM_CHUNK, s)
        assert s % chunk == 0
        nc = s // chunk
        xs = tuple(
            t.reshape(b, nc, chunk, *t.shape[2:]).transpose(
                1, 0, 2, *range(3, t.ndim + 1))
            for t in (q, k, v, ig, logf))
        carry0 = (jnp.zeros((b, nh, dh, dh), jnp.float32),
                  jnp.zeros((b, nh, dh), jnp.float32),
                  jnp.full((b, nh), -1e30, jnp.float32))
        step = functools.partial(_mlstm_chunk, nh=nh, dh=dh)
        (c_f, n_f, m_f), hs = jax.lax.scan(jax.checkpoint(step), carry0, xs)
        h = hs.transpose(1, 0, 2, 3, 4).reshape(b, s, di)
        new_state = ({"C": c_f, "n": n_f, "m": m_f}
                     if mode == "prefill" else None)

    y = (h.astype(x.dtype) * jax.nn.silu(z))
    return qproj(y, p["down"], bits["lstm_down"]), new_state


def init_mlstm_state(cfg, batch: int) -> dict:
    nh = cfg.n_heads
    dh = cfg.xlstm_d_inner // nh
    return {"C": jnp.zeros((batch, nh, dh, dh), jnp.float32),
            "n": jnp.zeros((batch, nh, dh), jnp.float32),
            "m": jnp.full((batch, nh), -1e30, jnp.float32)}


# ------------------------------------------------------------------- sLSTM
def init_slstm(key, cfg) -> dict:
    d = cfg.d_model
    nh = cfg.n_heads
    dh = d // nh
    ks = jax.random.split(key, 2)
    return {
        "w": init_qdense(ks[0], d, 4 * d, cfg.param_dtype),
        "r": jax.random.normal(ks[1], (nh, dh, 4 * dh), cfg.param_dtype)
        * (dh ** -0.5),
        "r_sw": jnp.float32(0.01),
        "r_sa": jnp.float32(0.05),
    }


def slstm_apply(p, x, bits, cfg, mode: str, state, ctx=None):
    """x: (B, S, d). bits: {'lstm_w','lstm_r'}. Sequential recurrence.

    Under a mesh, the recurrence runs inside shard_map over the batch axes:
    the recurrent weight R is a constant of the time scan, and GSPMD would
    otherwise resolve its partial gradient to replicated *inside* the loop —
    one (nh, dh, 4dh) all-reduce per timestep (96% of the xlstm train wire,
    EXPERIMENTS.md §Perf B2). Shard-local AD accumulates dR locally and
    psums once at the shard_map transpose boundary.
    """
    b, s, d = x.shape
    nh = cfg.n_heads
    dh = d // nh

    wx = qproj(x, p["w"], bits["lstm_w"]).astype(jnp.float32)   # (B,S,4d)
    r_q = quant.lsq_fake_quant(p["r"].astype(jnp.float32),
                               p["r_sw"], bits["lstm_r"])

    def cell(carry, wx_t):
        c, n, h, m = carry                                      # (b,nh,dh)…
        hq = quant.lsq_fake_quant(h, p["r_sa"], bits["lstm_r"])
        rh = jnp.einsum("bhd,hde->bhe", hq, r_q)                # (b,nh,4dh)
        raw = wx_t.reshape(wx_t.shape[0], nh, 4 * dh) + rh
        zt, it, ft, ot = jnp.split(raw, 4, axis=-1)             # (b,nh,dh)
        m_new = jnp.maximum(ft + m, it)
        ip = jnp.exp(it - m_new)
        fp = jnp.exp(ft + m - m_new)
        c_new = fp * c + ip * jnp.tanh(zt)
        n_new = fp * n + ip
        h_new = jax.nn.sigmoid(ot) * c_new / jnp.maximum(n_new, 1e-6)
        return (c_new, n_new, h_new, m_new), h_new

    if mode == "decode":
        c0 = (state["c"], state["n"], state["h"], state["m"])
        carry, hs = jax.lax.scan(cell, c0, wx.transpose(1, 0, 2))
        new_state = {"c": carry[0], "n": carry[1], "h": carry[2],
                     "m": carry[3]}
        h_all = hs.transpose(1, 0, 2, 3).reshape(b, s, d)
        return h_all.astype(x.dtype), new_state

    chunk = min(SLSTM_CHUNK, s)
    assert s % chunk == 0
    nc = s // chunk

    def run_scan(wx_in, r_unused):
        bl = wx_in.shape[0]
        z0 = jnp.zeros((bl, nh, dh), jnp.float32)
        m0 = jnp.full((bl, nh, dh), -1e30, jnp.float32)

        def chunk_step(carry, wx_c):                            # (bl,L,4d)
            carry, hs = jax.lax.scan(cell, carry, wx_c.transpose(1, 0, 2))
            return carry, hs

        xs = wx_in.reshape(bl, nc, chunk, 4 * d).transpose(1, 0, 2, 3)
        carry, hs = jax.lax.scan(jax.checkpoint(chunk_step),
                                 (z0, z0, z0, m0), xs)
        h_all = hs.transpose(2, 0, 1, 3, 4).reshape(bl, s, d)
        return h_all, carry

    batch_shardable = (ctx is not None and ctx.mesh is not None
                       and b % max(ctx.batch_size, 1) == 0
                       and ctx.batch_size > 1)
    if batch_shardable:
        from jax.sharding import PartitionSpec as P
        bspec = ctx.batch_spec
        h_all, carry = jax.shard_map(
            run_scan, mesh=ctx.mesh,
            in_specs=(P(bspec, None, None), P()),
            out_specs=(P(bspec, None, None),
                       (P(bspec), P(bspec), P(bspec), P(bspec))),
            check_vma=False,
        )(wx, 0.0)
    else:
        h_all, carry = run_scan(wx, 0.0)

    new_state = ({"c": carry[0], "n": carry[1], "h": carry[2],
                  "m": carry[3]} if mode == "prefill" else None)
    return h_all.astype(x.dtype), new_state


def init_slstm_state(cfg, batch: int) -> dict:
    nh = cfg.n_heads
    dh = cfg.d_model // nh
    z = jnp.zeros((batch, nh, dh), jnp.float32)
    return {"c": z, "n": z, "h": z,
            "m": jnp.full((batch, nh, dh), -1e30, jnp.float32)}
