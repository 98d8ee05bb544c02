"""Public jit'd wrappers: the one place that picks interpret mode.

Every Pallas kernel entry point takes ``interpret`` without a default;
these wrappers pass ``not _on_tpu()``, so a kernel is compiled by Mosaic
on TPU and runs in the Pallas interpreter on CPU (validation). The jnp
references are re-exported alongside."""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mlstm_kernel import mlstm_chunkwise
from repro.kernels.paged_attention import paged_attention
from repro.kernels.quant_matmul import quant_matmul
from repro.kernels.ragged_decode_attention import ragged_decode_attention
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.xfer_matmul import xfer_matmul


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def matmul(x, w, *, tr=256, tm=256, tn=256):
    return xfer_matmul(x, w, tr=tr, tm=tm, tn=tn, interpret=not _on_tpu())


def int8_matmul(x, w_q, scale, *, tr=256, tm=256, tn=256):
    return quant_matmul(x, w_q, scale, tr=tr, tm=tm, tn=tn,
                        interpret=not _on_tpu())


def attention(q, k, v, *, k_scale=None, v_scale=None, causal=True, window=0,
              bq=512, bk=512):
    return flash_attention(q, k, v, k_scale=k_scale, v_scale=v_scale,
                           causal=causal, window=window, bq=bq, bk=bk,
                           interpret=not _on_tpu())


def lru_scan(a, b, h0, *, bs=256):
    return rglru_scan(a, b, h0, bs=bs, interpret=not _on_tpu())


def paged_attn(q, kp, vp, page_table, lengths, *, k_scale=None, v_scale=None):
    return paged_attention(q, kp, vp, page_table, lengths,
                           k_scale=k_scale, v_scale=v_scale,
                           interpret=not _on_tpu())


def ragged_decode_attn(q, k, v, pos, layer, q_pos, *, window=0):
    return ragged_decode_attention(q, k, v, pos, layer, q_pos, window=window,
                                   interpret=not _on_tpu())


def mlstm(q, k, v, it, ft, *, bq=256):
    return mlstm_chunkwise(q, k, v, it, ft, bq=bq, interpret=not _on_tpu())


# references re-exported for tests/benchmarks
matmul_ref = ref.matmul_ref
int8_matmul_ref = ref.quant_matmul_ref
attention_ref = ref.flash_attention_ref
lru_scan_ref = ref.rglru_scan_ref
mlstm_ref = ref.mlstm_ref
paged_attn_ref = ref.paged_attention_ref
ragged_decode_attn_ref = ref.ragged_decode_attention_ref
