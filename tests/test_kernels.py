"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


_TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
        jnp.bfloat16: dict(rtol=5e-2, atol=5e-2)}


@pytest.mark.parametrize("r,n,m,tiles", [
    (256, 256, 256, (128, 128, 128)),
    (512, 384, 128, (128, 128, 128)),
    (128, 128, 512, (64, 64, 256)),
    (384, 128, 384, (384, 128, 128)),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_xfer_matmul(r, n, m, tiles, dtype, key):
    k1, k2 = jax.random.split(key)
    x = _rand(k1, (r, n), dtype)
    w = _rand(k2, (n, m), dtype)
    tr, tn, tm = tiles
    out = ops.matmul(x, w, tr=tr, tn=tn, tm=tm)
    ref = ops.matmul_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_TOL[dtype])


@pytest.mark.parametrize("s,t,d,blocks,window", [
    (256, 256, 64, (128, 128), 0),
    (128, 128, 32, (64, 32), 0),
    (256, 256, 64, (64, 64), 64),
    (64, 256, 64, (64, 128), 0),  # cross/short-query
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(s, t, d, blocks, window, dtype, key):
    k1, k2, k3 = jax.random.split(key, 3)
    q = _rand(k1, (3, s, d), dtype)
    k = _rand(k2, (3, t, d), dtype)
    v = _rand(k3, (3, t, d), dtype)
    causal = s == t
    out = ops.attention(q, k, v, causal=causal, window=window,
                        bq=blocks[0], bk=blocks[1])
    ref = ops.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_TOL[dtype])


@pytest.mark.parametrize("b,s,w,bs", [(2, 256, 128, 64), (1, 128, 256, 128),
                                      (3, 512, 64, 256)])
def test_rglru_scan(b, s, w, bs, key):
    k1, k2, k3 = jax.random.split(key, 3)
    a = jax.nn.sigmoid(jax.random.normal(k1, (b, s, w)))
    bb = jax.random.normal(k2, (b, s, w))
    h0 = jax.random.normal(k3, (b, w))
    out = ops.lru_scan(a, bb, h0, bs=bs)
    ref = ops.lru_scan_ref(a, bb, h0)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bh,s,d,bq", [(2, 128, 32, 32), (1, 256, 64, 64),
                                       (4, 64, 16, 64)])
def test_mlstm_chunkwise(bh, s, d, bq, key):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (bh, s, d))
    k = jax.random.normal(ks[1], (bh, s, d)) / np.sqrt(d)
    v = jax.random.normal(ks[2], (bh, s, d))
    it = jax.random.normal(ks[3], (bh, s))
    ft = jax.random.normal(ks[4], (bh, s)) + 2.0
    out = ops.mlstm(q, k, v, it, ft, bq=bq)
    ref = ops.mlstm_ref(q, k, v, it, ft)
    np.testing.assert_allclose(out, ref, rtol=3e-3, atol=3e-3)


@pytest.mark.parametrize("b,h,g,d,ps,m", [(3, 8, 2, 16, 8, 4),
                                          (2, 4, 4, 32, 16, 2),
                                          (1, 6, 1, 64, 8, 3)])
def test_paged_attention(b, h, g, d, ps, m, key):
    """Scalar-prefetch paged decode kernel vs the gather-then-attend
    oracle: GQA head grouping, partial frontier pages (length masking)
    and arbitrary page-table permutations."""
    ks = jax.random.split(key, 3)
    n_pages = b * m + 2
    q = jax.random.normal(ks[0], (b, h, d))
    kp = jax.random.normal(ks[1], (n_pages, ps, g, d))
    vp = jax.random.normal(ks[2], (n_pages, ps, g, d))
    rng = np.random.RandomState(0)
    table = np.stack([rng.permutation(np.arange(1, n_pages))[:m]
                      for _ in range(b)])
    # partial page / mid / full extents
    lengths = rng.randint(1, m * ps + 1, size=b).astype(np.int32)
    lengths[-1] = m * ps
    out = ops.paged_attn(q, kp, vp, jnp.asarray(table), jnp.asarray(lengths))
    ref = ops.paged_attn_ref(q, kp, vp, jnp.asarray(table),
                             jnp.asarray(lengths))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("r,n,m,tiles", [
    (256, 256, 256, (128, 128, 128)),
    (512, 384, 128, (128, 128, 128)),
    (128, 128, 512, (64, 64, 256)),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul(r, n, m, tiles, dtype, key):
    """Dequant-fused int8 matmul vs dequantize-then-matmul oracle: the
    per-output-channel scale is applied once at the accumulator flush
    ((x @ q) * s == x @ (q * s)), so results match the oracle to fp
    accumulation error."""
    from repro import quant as Q
    k1, k2 = jax.random.split(key)
    x = _rand(k1, (r, n), dtype)
    t = Q.quantize(jax.random.normal(k2, (n, m), jnp.float32), axis=0)
    scale = t.scale.reshape(1, m)
    tr, tn, tm = tiles
    out = ops.int8_matmul(x, t.q, scale, tr=tr, tn=tn, tm=tm)
    ref = ops.int8_matmul_ref(x, t.q, scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_TOL[dtype])


@pytest.mark.parametrize("s,t,d,blocks", [
    (256, 256, 64, (128, 128)),
    (128, 128, 32, (64, 32)),
    (64, 256, 64, (64, 128)),  # cross/short-query
])
def test_flash_attention_int8_kv(s, t, d, blocks, key):
    """Dequant-fused flash attention: int8 k/v + per-token scales flow
    through the online softmax identically to pre-dequantized fp k/v."""
    from repro import quant as Q
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (3, s, d))
    tk = Q.quantize_kv(jax.random.normal(k2, (3, t, d)))
    tv = Q.quantize_kv(jax.random.normal(k3, (3, t, d)))
    causal = s == t
    out = ops.attention(q, tk.q, tv.q, k_scale=tk.scale, v_scale=tv.scale,
                        causal=causal, bq=blocks[0], bk=blocks[1])
    ref = ops.attention_ref(q, tk.q, tv.q, k_scale=tk.scale,
                            v_scale=tv.scale, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("b,h,g,d,ps,m", [(3, 8, 2, 16, 8, 4),
                                          (2, 4, 4, 32, 16, 2)])
def test_paged_attention_int8_kv(b, h, g, d, ps, m, key):
    """Paged decode kernel over int8 page pools: the per-token scale
    pages ride the same page-table indirection as k/v and dequantize in
    VMEM; output matches the gather-dequant-attend oracle."""
    from repro import quant as Q
    ks = jax.random.split(key, 3)
    n_pages = b * m + 2
    q = jax.random.normal(ks[0], (b, h, d))
    tk = Q.quantize_kv(jax.random.normal(ks[1], (n_pages, ps, g, d)))
    tv = Q.quantize_kv(jax.random.normal(ks[2], (n_pages, ps, g, d)))
    rng = np.random.RandomState(0)
    table = np.stack([rng.permutation(np.arange(1, n_pages))[:m]
                      for _ in range(b)])
    lengths = rng.randint(1, m * ps + 1, size=b).astype(np.int32)
    lengths[-1] = m * ps
    out = ops.paged_attn(q, tk.q, tv.q, jnp.asarray(table),
                         jnp.asarray(lengths),
                         k_scale=tk.scale, v_scale=tv.scale)
    ref = ops.paged_attn_ref(q, tk.q, tv.q, jnp.asarray(table),
                             jnp.asarray(lengths), tk.scale, tv.scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_model_attention_matches_kernel(key):
    """models/layers.attention (jnp path) == flash kernel on plain causal."""
    from repro.models import layers as L
    k1, k2, k3 = jax.random.split(key, 3)
    b, s, h, d = 2, 128, 4, 32
    q = jax.random.normal(k1, (b, s, h, d))
    k = jax.random.normal(k2, (b, s, h, d))
    v = jax.random.normal(k3, (b, s, h, d))
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    out_model = L.attention(q, k.swapaxes(1, 2), v.swapaxes(1, 2), pos, pos,
                            causal=True, q_block=64)  # K/V head-major
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    out_kernel = ops.attention(qf, kf, vf, causal=True, bq=64, bk=64)
    out_kernel = out_kernel.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(out_model, out_kernel, rtol=2e-4, atol=2e-4)


def _ragged_grid(key, *, h, g, q_pos, t=384, d=128, layers=3, window=0):
    """A stacked head-major grid ``[L, B, G, t, d]`` (bf16) as the decode
    scan carries it, with each row's ring filled through ``q_pos``: slot
    ``s`` holds the newest position ``≡ s (mod t)`` at or below it. Past a
    row's extent, odd rows hold stale positions above ``q_pos`` and even
    rows empty slots (−1), both over finite garbage of magnitude 1e4 that
    the mask must keep out."""
    b = len(q_pos)
    kq, kk, kv = jax.random.split(key, 3)
    q = _rand(kq, (b, h, d), jnp.bfloat16)
    k = _rand(kk, (layers, b, g, t, d), jnp.float32)
    v = _rand(kv, (layers, b, g, t, d), jnp.float32)
    qp = np.asarray(q_pos)[:, None]
    s = np.arange(t)[None]
    newest = qp - np.mod(qp - s, t)
    live = newest >= 0
    stale = np.where(np.arange(b)[:, None] % 2 == 1, s + t, -1)
    pos = np.where(live, newest, stale).astype(np.int32)
    junk = jnp.asarray(~live)[None, :, None, :, None]
    k = jnp.where(junk, 1e4, k).astype(jnp.bfloat16)
    v = jnp.where(junk, -1e4, v).astype(jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.asarray(pos)[None], (layers, b, t))
    return q, k, v, pos, jnp.asarray(q_pos, jnp.int32)


# (query heads, K/V heads): GQA rep 8 and rep 1
_RAGGED_HEADS = {"rep8": (16, 2), "rep1": (4, 4)}
# each row's query position; tb = 128 on a ring of 384
_RAGGED_CASES = {
    # extents 0, 1, tb-1, tb, tb+1 and t
    "extents": (dict(q_pos=[-1, 0, 126, 127, 128, 383]), 1),
    "layer0": (dict(q_pos=[5, 200, 300]), 0),
    # rings that wrapped, read whole; with a window the mask cuts them
    "wrapped": (dict(q_pos=[384, 500, 1000, 30]), 2),
    "wrapped-window": (dict(q_pos=[384, 500, 1000, 30], window=200), 2),
}


@pytest.mark.parametrize("heads", list(_RAGGED_HEADS))
@pytest.mark.parametrize("case", list(_RAGGED_CASES))
def test_ragged_decode_attention(case, heads, key):
    """The ragged kernel against ``layers.attention`` over the layer's
    whole slab of the stacked head-major grid, masked as the decode path
    masks it: equal within the flash kernel's bf16 tolerance, garbage
    past each row's extent kept out, and a row with no live block
    (extent 0) reads finite zeros."""
    from repro.models import layers as L
    kw, layer = _RAGGED_CASES[case]
    h, g = _RAGGED_HEADS[heads]
    window = kw.get("window", 0)
    q, k, v, pos, q_pos = _ragged_grid(key, h=h, g=g, **kw)
    out = ops.ragged_decode_attn(q, k, v, pos, jnp.int32(layer), q_pos,
                                 window=window)
    want = L.attention(q[:, None], k[layer], v[layer], q_pos[:, None],
                       pos[layer], pos[layer] >= 0, window=window)[:, 0]
    out, want = np.asarray(out, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(out).all()
    empty = np.asarray(q_pos) < 0
    np.testing.assert_array_equal(out[empty], 0.0)
    np.testing.assert_allclose(out[~empty], want[~empty],
                               **_TOL[jnp.bfloat16])


def test_ragged_decode_attention_ref(key):
    """The jnp oracle in ``kernels/ref.py`` (whole slab, float32) and the
    kernel agree on every case at once: extents, a wrapped row, a window."""
    q, k, v, pos, q_pos = _ragged_grid(
        key, h=16, g=2, q_pos=[-1, 0, 127, 128, 383, 700])
    for window in (0, 100):
        out = ops.ragged_decode_attn(q, k, v, pos, jnp.int32(2), q_pos,
                                     window=window)
        ref = ops.ragged_decode_attn_ref(q, k, v, pos, 2, q_pos,
                                         window=window)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   **_TOL[jnp.bfloat16])
