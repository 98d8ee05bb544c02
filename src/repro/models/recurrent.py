"""Recurrent blocks: RG-LRU (RecurrentGemma/Griffin), mLSTM and sLSTM (xLSTM).

Train/prefill use parallel forms (associative scan for RG-LRU, decay-biased
chunked attention for mLSTM, time scan for sLSTM); decode uses O(1)
recurrent state updates. The two forms are numerically cross-checked by
property tests (tests/test_recurrent_parity.py).

**Pad-free prefill** (``seq_lens``): every parallel form accepts a per-row
true length for right-padded batches and stops integrating the padded
tail into the recurrent state — RG-LRU forces identity scan elements
``(a, b) = (1, 0)`` on padded steps, mLSTM forces identity gates
``(log f, i) = (0, -1e30)`` so padded steps carry zero weight in the
state fold, and sLSTM carries the previous state through masked steps.
The resulting state is bit-equal to running the unpadded prompt, for
*any* padded length — which is what lets the serving scheduler prefill
recurrent archs at power-of-two buckets instead of ``max_len``.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import layers as L

_LRU_C = 8.0

_NEG = -1e30  # log-space "never": exp(_NEG - finite) underflows to exactly 0


def _valid_mask(seq_lens: Optional[jax.Array], s: int) -> Optional[jax.Array]:
    """[B, S] bool — True where the position is below the row's true
    length; None when no per-row lengths were given (nothing padded)."""
    if seq_lens is None:
        return None
    return jnp.arange(s)[None, :] < seq_lens[:, None]


# ---------------------------------------------------------------------------
# RG-LRU block (Griffin recurrent block: in-proj → conv1d → RG-LRU → gate)
# ---------------------------------------------------------------------------

def rglru_init(key, arch: ArchConfig, dtype=jnp.float32) -> dict:
    d = arch.d_model
    w = arch.lru_width or d
    heads = arch.num_heads
    hw = w // heads
    cw = arch.conv1d_width or 4
    ks = jax.random.split(key, 8)
    p = {
        "ln1": jnp.zeros((d,), dtype),
        "w_in": L.dense_init(ks[0], (d, 2 * w), 0, dtype),
        "conv_w": L.dense_init(ks[1], (cw, w), 0, dtype),
        "conv_b": jnp.zeros((w,), dtype),
        # block-diagonal per-head input/recurrence gates
        "gate_w": L.dense_init(ks[2], (heads, hw, 2 * hw), 1, dtype),
        "gate_b": jnp.zeros((heads, 2 * hw), dtype),
        # Λ init: a host constant, so jitted and eager init agree bitwise
        "a_param": jnp.asarray(np.linspace(0.9, 0.999, w), dtype),
        "w_out": L.dense_init(ks[3], (w, d), 0, dtype),
    }
    if arch.d_ff and arch.mlp != "none":
        p["ln2"] = jnp.zeros((d,), dtype)
        p["mlp"] = L.mlp_init(ks[4], d, arch.d_ff, arch.mlp, dtype)
    return p


def rglru_dims(arch: ArchConfig) -> dict:
    d = {
        "ln1": (None,),
        "w_in": ("xfer", "tp"),
        "conv_w": (None, "tp"),
        "conv_b": ("tp",),
        "gate_w": ("tp", None, None),
        "gate_b": ("tp", None),
        "a_param": ("tp",),
        "w_out": ("tp", "xfer"),
    }
    if arch.d_ff and arch.mlp != "none":
        d["ln2"] = (None,)
        d["mlp"] = L.mlp_dims(arch.mlp)
    return d


def make_rglru_state(arch: ArchConfig, batch: int, dtype=jnp.float32) -> dict:
    w = arch.lru_width or arch.d_model
    cw = arch.conv1d_width or 4
    return {"h": jnp.zeros((batch, w), jnp.float32),
            "conv": jnp.zeros((batch, cw - 1, w), dtype)}


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 state: Optional[jax.Array],
                 seq_lens: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv1d. x:[B,S,W], w:[cw,W]. Returns (y, new_state).

    ``seq_lens`` makes the carried state length-exact for right-padded
    rows: the window of the last ``cw-1`` *real* inputs is
    ``xp[len : len+cw-1]`` (``xp`` index ``i`` holds input ``i-(cw-1)``),
    instead of the padded tail the suffix slice would keep.
    """
    cw = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], cw - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)  # [B, S+cw-1, W]
    y = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None, :] for i in range(cw))
    if cw <= 1:
        return y + b, state
    if seq_lens is None:
        return y + b, xp[:, -(cw - 1):, :]
    new_state = jax.vmap(
        lambda row, l: jax.lax.dynamic_slice_in_dim(row, l, cw - 1, axis=0)
    )(xp, seq_lens)
    return y + b, new_state


def _rglru_gates(p: dict, xr: jax.Array, heads: int):
    b, s, w = xr.shape
    hw = w // heads
    xh = xr.reshape(b, s, heads, hw)
    g = jnp.einsum("bshd,hde->bshe", xh, p["gate_w"]) + p["gate_b"]
    r, i = jnp.split(g.reshape(b, s, 2 * w), 2, axis=-1)
    r, i = jax.nn.sigmoid(r.astype(jnp.float32)), jax.nn.sigmoid(i.astype(jnp.float32))
    log_a = -_LRU_C * jax.nn.softplus(p["a_param"].astype(jnp.float32)) * r
    gated_x = xr.astype(jnp.float32) * i
    scale = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    return log_a, scale * gated_x


def rglru_apply(arch: ArchConfig, p: dict, x: jax.Array, ctx=None, *,
                state: Optional[dict] = None,
                seq_lens: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Optional[dict]]:
    b, s, d = x.shape
    h = L.rms_norm(x, p["ln1"])
    u = h @ p["w_in"]
    if ctx is not None:
        u = ctx.constrain(u, "batch", "seq", "tp")
    y_branch, xr = jnp.split(u, 2, axis=-1)

    conv_state = state["conv"] if state is not None else None
    xr, new_conv = _causal_conv(xr, p["conv_w"], p["conv_b"], conv_state,
                                seq_lens=None if s == 1 else seq_lens)
    log_a, bx = _rglru_gates(p, xr, arch.num_heads)

    if s == 1 and state is not None:  # decode step
        a = jnp.exp(log_a[:, 0])
        h_new = a * state["h"] + bx[:, 0]
        seq = h_new[:, None, :]
        new_state = {"h": h_new, "conv": new_conv}
    else:
        valid = _valid_mask(seq_lens, s)
        if valid is not None:
            # padded steps become scan identities (a, b) = (1, 0): the
            # carried h past the true length is exactly h_{len-1}
            log_a = jnp.where(valid[:, :, None], log_a, 0.0)
            bx = jnp.where(valid[:, :, None], bx, 0.0)
        a = jnp.exp(log_a)
        if state is not None:
            bx = bx.at[:, 0].add(a[:, 0] * state["h"])

        def comb(c1, c2):
            a1, b1 = c1
            a2, b2 = c2
            return a1 * a2, a2 * b1 + b2

        _, seq = jax.lax.associative_scan(comb, (a, bx), axis=1)
        new_state = ({"h": seq[:, -1], "conv": new_conv}
                     if state is not None else None)

    out = (seq.astype(x.dtype) * jax.nn.gelu(y_branch, approximate=True)) @ p["w_out"]
    x = x + out
    if ctx is not None:
        x = ctx.constrain(x, "batch", "sp", None)
    if "ln2" in p:
        x = x + L.mlp_apply(p["mlp"], L.rms_norm(x, p["ln2"]), arch.mlp, ctx)
        if ctx is not None:
            x = ctx.constrain(x, "batch", "sp", None)
    return x, new_state


# ---------------------------------------------------------------------------
# mLSTM block (xLSTM): matrix memory, decay-biased attention parallel form
# ---------------------------------------------------------------------------

def mlstm_init(key, arch: ArchConfig, dtype=jnp.float32) -> dict:
    d = arch.d_model
    w = 2 * d  # expansion factor 2
    heads = arch.num_heads
    ks = jax.random.split(key, 8)
    return {
        "ln1": jnp.zeros((d,), dtype),
        "w_up": L.dense_init(ks[0], (d, 2 * w), 0, dtype),
        "wq": L.dense_init(ks[1], (w, w), 0, dtype),
        "wk": L.dense_init(ks[2], (w, w), 0, dtype),
        "wv": L.dense_init(ks[3], (w, w), 0, dtype),
        "w_i": L.dense_init(ks[4], (w, heads), 0, dtype),
        "w_f": L.dense_init(ks[5], (w, heads), 0, dtype),
        "b_i": jnp.zeros((heads,), dtype),
        "b_f": jnp.full((heads,), 3.0, dtype),  # forget-gate bias: remember
        "ln_inner": jnp.zeros((w,), dtype),
        "w_down": L.dense_init(ks[6], (w, d), 0, dtype),
    }


def mlstm_dims(arch: ArchConfig) -> dict:
    return {
        "ln1": (None,), "w_up": ("xfer", "tp"),
        "wq": ("xfer", "tp"), "wk": ("xfer", "tp"), "wv": ("xfer", "tp"),
        "w_i": ("xfer", "tp"), "w_f": ("xfer", "tp"),
        "b_i": ("tp",), "b_f": ("tp",),
        "ln_inner": ("tp",), "w_down": ("tp", "xfer"),
    }


def make_mlstm_state(arch: ArchConfig, batch: int) -> dict:
    w = 2 * arch.d_model
    heads = arch.num_heads
    hd = w // heads
    return {"C": jnp.zeros((batch, heads, hd, hd), jnp.float32),
            "n": jnp.zeros((batch, heads, hd), jnp.float32),
            "m": jnp.full((batch, heads), -1e30, jnp.float32)}


def _mlstm_qkvif(arch: ArchConfig, p: dict, u: jax.Array):
    b, s, w = u.shape
    heads = arch.num_heads
    hd = w // heads
    q = (u @ p["wq"]).reshape(b, s, heads, hd)
    k = (u @ p["wk"]).reshape(b, s, heads, hd) / math.sqrt(hd)
    v = (u @ p["wv"]).reshape(b, s, heads, hd)
    it = (u @ p["w_i"] + p["b_i"]).astype(jnp.float32)  # [B,S,H]
    ft = (u @ p["w_f"] + p["b_f"]).astype(jnp.float32)
    return q, k, v, it, ft


def mlstm_apply(arch: ArchConfig, p: dict, x: jax.Array, ctx=None, *,
                state: Optional[dict] = None,
                seq_lens: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Optional[dict]]:
    b, s, d = x.shape
    h0 = L.rms_norm(x, p["ln1"])
    up = h0 @ p["w_up"]
    if ctx is not None:
        up = ctx.constrain(up, "batch", "seq", "tp")
    u, z = jnp.split(up, 2, axis=-1)  # mixer input, output gate branch
    q, k, v, it, ft = _mlstm_qkvif(arch, p, u)
    heads = arch.num_heads
    hd = u.shape[-1] // heads

    if s == 1 and state is not None:  # recurrent decode
        logf = jax.nn.log_sigmoid(ft[:, 0])  # [B,H]
        m_new = jnp.maximum(logf + state["m"], it[:, 0])
        fs = jnp.exp(logf + state["m"] - m_new)[..., None]
        is_ = jnp.exp(it[:, 0] - m_new)[..., None]
        kf = k[:, 0].transpose(0, 2, 1).astype(jnp.float32)  # [B,hd? no
        k1 = k[:, 0].astype(jnp.float32)  # [B,H,hd]
        v1 = v[:, 0].astype(jnp.float32)
        C = fs[..., None] * state["C"] + is_[..., None] * (k1[..., :, None] * v1[..., None, :])
        n = fs * state["n"] + is_ * k1
        q1 = q[:, 0].astype(jnp.float32)
        num = jnp.einsum("bhkv,bhk->bhv", C, q1)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", n, q1)),
                          jnp.exp(-m_new))[..., None]
        hseq = (num / den).reshape(b, 1, heads * hd)
        new_state = {"C": C, "n": n, "m": m_new}
    else:
        # chunkwise parallel form: intra-chunk decay-biased attention +
        # cross-chunk recurrent state (keeps memory O(S·Q), not O(S²)).
        st0 = state if state is not None else make_mlstm_state(arch, b)
        logf = jax.nn.log_sigmoid(ft)  # [B,S,H]
        valid = _valid_mask(seq_lens, s)
        if valid is not None:
            # identity gates on padded steps: forget=1 (log f = 0) keeps
            # the cumulative decay F flat past the true length, and the
            # _NEG input gate gives the step weight exp(_NEG - m) == 0 in
            # the state fold — padded k/v never enter (C, n, m)
            logf = jnp.where(valid[..., None], logf, 0.0)
            it = jnp.where(valid[..., None], it, _NEG)
        chunk = min(s, 1024)
        while s % chunk:
            chunk -= 1
        nb = s // chunk

        def chunk_body(carry, inp):
            # "flashattn" scope: VMEM-resident in the mlstm Pallas kernel
            qc, kc, vc, ic, fc = inp  # [B,Q,H,*]
            F = jnp.cumsum(fc, axis=1)  # [B,Q,H]
            Ft = F.transpose(0, 2, 1)  # [B,H,Q]
            it_t = ic.transpose(0, 2, 1)
            bias = Ft[:, :, :, None] - Ft[:, :, None, :] + it_t[:, :, None, :]
            causal = jnp.tril(jnp.ones((chunk, chunk), bool))
            bias = jnp.where(causal[None, None], bias, -jnp.inf)
            w_state = Ft + carry["m"][:, :, None]  # [B,H,Q]
            m_i = jnp.maximum(jnp.max(bias, axis=-1), w_state)
            m_i = jnp.maximum(m_i, -1e30)
            dmat = jnp.exp(bias - m_i[..., None])
            qf, kf, vf = (t.astype(jnp.float32) for t in (qc, kc, vc))
            scores = jnp.einsum("bqhd,bthd->bhqt", qf, kf) * dmat
            s_coef = jnp.exp(w_state - m_i)  # [B,H,Q]
            num = (jnp.einsum("bhqt,bthd->bqhd", scores, vf)
                   + jnp.einsum("bhq,bhkv,bqhk->bqhv", s_coef, carry["C"], qf))
            den = (jnp.einsum("bhqt->bhq", scores)
                   + s_coef * jnp.einsum("bhk,bqhk->bhq", carry["n"], qf))
            den = jnp.maximum(jnp.abs(den), jnp.exp(-m_i)).transpose(0, 2, 1)
            out = num / den[..., None]  # [B,Q,H,hd]
            nxt = _mlstm_suffix_state(arch, carry, kc, vc, ic, fc)
            return nxt, out

        def rs(t):  # [B,S,...] -> [nb,B,Q,...]
            return t.reshape(b, nb, chunk, *t.shape[2:]).transpose(1, 0, 2, *range(3, t.ndim + 1))

        st2, outs = jax.lax.scan(
            chunk_body, st0, (rs(q), rs(k), rs(v), rs(it), rs(logf)))
        hseq = outs.transpose(1, 0, 2, 3, 4).reshape(b, s, heads * hd)
        new_state = st2 if state is not None else None

    hseq = L.rms_norm(hseq.astype(x.dtype), p["ln_inner"])
    out = (hseq * jax.nn.silu(z)) @ p["w_down"]
    x = x + out
    if ctx is not None:
        x = ctx.constrain(x, "batch", "sp", None)
    return x, new_state


def _mlstm_suffix_state(arch, state, k, v, it, logf):
    """Fold a full sequence into the recurrent state (prefill → decode)."""
    b, s, heads, hd = k.shape
    F = jnp.cumsum(logf, axis=1)  # [B,S,H]
    Fe = F[:, -1][:, None]  # [B,1,H]
    w_log = (Fe - F + it)  # weight of step t in final state (log)
    m_new = jnp.maximum(jnp.max(w_log, axis=1), Fe[:, 0] + state["m"])  # [B,H]
    wts = jnp.exp(w_log - m_new[:, None, :])  # [B,S,H]
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    C = jnp.einsum("bsh,bshk,bshv->bhkv", wts, kf, vf)
    n = jnp.einsum("bsh,bshk->bhk", wts, kf)
    carry = jnp.exp(Fe[:, 0] + state["m"] - m_new)
    C = C + carry[..., None, None] * state["C"]
    n = n + carry[..., None] * state["n"]
    return {"C": C, "n": n, "m": m_new}


# ---------------------------------------------------------------------------
# sLSTM block (xLSTM): scalar memory, strictly sequential scan
# ---------------------------------------------------------------------------

def slstm_init(key, arch: ArchConfig, dtype=jnp.float32) -> dict:
    d = arch.d_model
    heads = arch.num_heads
    hd = d // heads
    ks = jax.random.split(key, 4)
    return {
        "ln1": jnp.zeros((d,), dtype),
        "w": L.dense_init(ks[0], (d, 4 * d), 0, dtype),
        "r": L.dense_init(ks[1], (heads, hd, 4 * hd), 1, dtype),
        "b": jnp.zeros((4 * d,), dtype),
        "w_out": L.dense_init(ks[2], (d, d), 0, dtype),
    }


def slstm_dims(arch: ArchConfig) -> dict:
    return {"ln1": (None,), "w": ("xfer", "tp"), "r": ("tp", None, None),
            "b": ("tp",), "w_out": ("xfer", "tp")}


def make_slstm_state(arch: ArchConfig, batch: int) -> dict:
    d = arch.d_model
    z = jnp.zeros((batch, d), jnp.float32)
    return {"c": z, "n": z, "h": z, "m": jnp.full((batch, d), -1e30, jnp.float32)}


def _slstm_step(arch: ArchConfig, p: dict, state: dict, xt: jax.Array):
    """One timestep. xt: [B, 4D] pre-activations from the input proj."""
    b = xt.shape[0]
    d = arch.d_model
    heads = arch.num_heads
    hd = d // heads
    hprev = state["h"].reshape(b, heads, hd).astype(xt.dtype)
    rec = jnp.einsum("bhd,hde->bhe", hprev, p["r"]).reshape(b, 4 * d)
    pre = (xt + rec + p["b"]).astype(jnp.float32)
    i_, f_, z_, o_ = jnp.split(pre, 4, axis=-1)
    m_new = jnp.maximum(f_ + state["m"], i_)
    ip = jnp.exp(i_ - m_new)
    fp = jnp.exp(f_ + state["m"] - m_new)
    c = fp * state["c"] + ip * jnp.tanh(z_)
    n = fp * state["n"] + ip
    h = jax.nn.sigmoid(o_) * c / jnp.maximum(n, 1e-6)
    return {"c": c, "n": n, "h": h, "m": m_new}


def slstm_apply(arch: ArchConfig, p: dict, x: jax.Array, ctx=None, *,
                state: Optional[dict] = None,
                seq_lens: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Optional[dict]]:
    b, s, d = x.shape
    h0 = L.rms_norm(x, p["ln1"])
    pre = h0 @ p["w"]  # [B,S,4D]
    if ctx is not None:
        pre = ctx.constrain(pre, "batch", "seq", "tp")
    st = state if state is not None else make_slstm_state(arch, b)

    if s == 1:
        st2 = _slstm_step(arch, p, st, pre[:, 0])
        seq = st2["h"][:, None].astype(x.dtype)
        new_state = st2 if state is not None else None
    else:
        valid = _valid_mask(seq_lens, s)

        def body(carry, inp):
            xt, vt = inp
            nxt = _slstm_step(arch, p, carry, xt)
            if vt is not None:
                # mask-carry: padded steps pass the state (incl. h, which
                # feeds the recurrence matrix) through untouched
                nxt = jax.tree.map(
                    lambda n, c: jnp.where(vt[:, None], n, c), nxt, carry)
            return nxt, nxt["h"]

        xs = (pre.transpose(1, 0, 2),
              valid.transpose(1, 0) if valid is not None else None)
        if valid is None:
            st2, hs = jax.lax.scan(lambda c, xt: body(c, (xt, None)), st, xs[0])
        else:
            st2, hs = jax.lax.scan(body, st, xs)
        seq = hs.transpose(1, 0, 2).astype(x.dtype)
        new_state = st2 if state is not None else None

    x = x + seq @ p["w_out"]
    if ctx is not None:
        x = ctx.constrain(x, "batch", "sp", None)
    return x, new_state
