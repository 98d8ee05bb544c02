"""Shared symmetric INT8 quantization — the serving path's bytes lever.

The repo already quantised Adam states (``optim/adamw.py``) and DP
gradients (``runtime/compression.py``) with two ad-hoc copies of the
same round-to-int8 routine; this module is the single implementation
both now route through, extended with the per-channel / per-token modes
the INT8 *serving* path needs (the SNIPPETS exemplar's FP32→INT8
quantize-and-compile pipeline, and the single highest-leverage
bandwidth optimisation both FPGA surveys in PAPERS.md identify).

Contract
--------
``quantize`` is symmetric: ``q = clip(round(x / scale), -127, 127)``
with ``scale = amax / 127`` over the reduction axes. The clip is load-
bearing: fp rounding error at the amax element can produce 127.00...x
which ``round`` takes to 128 — int8 wrap-around to -128 flips the sign
of the largest-magnitude element (the historical ``adamw._quant`` bug).
Quantisation error is bounded by ``scale / 2`` per element inside the
representable range, and ``quantize(dequantize(t))`` is idempotent
(exact round trip of already-quantised values).

Three layouts, one code path:

* per-tensor   — ``axis=None``; scalar f32 scale (optimizer states,
  gradient compression).
* per-channel  — ``axis=<reduced axes>``; the scale keeps the operand's
  rank with reduced axes of extent 1, so ``q * scale`` broadcasts with
  no bookkeeping (serving weights: reduce all but the output-feature
  axis).
* per-token    — a per-channel special case over the head dimension
  (KV-cache rows: scale shaped ``[B, G, T, 1]`` rides next to the int8
  ``[B, G, T, D]`` cache leaf and pages/splices with it structurally).

``QuantConfig`` is the user surface (``ServeConfig(quant=...)``) and the
planner input (``capacity_bytes`` shrinks weight/KV bytes under it).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

PyTree = Any

#: symmetric int8 range bound (‑127..127; -128 is never produced)
Q_MAX = 127.0
#: amax floor so all-zero tensors quantise to scale Q_EPS/127, not 0/0
Q_EPS = 1e-12

Axis = Union[None, int, Tuple[int, ...]]


class QTensor(NamedTuple):
    """Symmetric int8 quantised tensor with an f32 scale.

    ``scale`` is scalar (per-tensor) or keeps ``q``'s rank with the
    reduced axes of extent 1 (per-channel), so ``q * scale`` always
    broadcasts directly."""

    q: jax.Array
    scale: jax.Array

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim


def is_qtensor(x) -> bool:
    return isinstance(x, QTensor)


def quantize(x: jax.Array, axis: Axis = None) -> QTensor:
    """Symmetric int8 quantisation over the ``axis`` reduction axes.

    ``axis=None`` → per-tensor (scalar scale); an int or tuple → the
    amax is taken over those axes and the scale keeps rank with extent-1
    reduced axes. The result is always clipped to ±127 (see module
    docstring: unclipped round can wrap the amax element to -128)."""
    x = x.astype(jnp.float32)
    if axis is None:
        amax = jnp.max(jnp.abs(x))
    else:
        amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = (jnp.maximum(amax, Q_EPS) / Q_MAX).astype(jnp.float32)
    q = jnp.clip(jnp.round(x / scale), -Q_MAX, Q_MAX).astype(jnp.int8)
    return QTensor(q, scale)


def dequantize(t: QTensor, dtype=None) -> jax.Array:
    out = t.q.astype(jnp.float32) * t.scale
    return out if dtype is None else out.astype(dtype)


# ---------------------------------------------------------------------------
# serving-weight quantisation: per-channel over the output-feature axis
# ---------------------------------------------------------------------------

def _weight_axis(x) -> Tuple[int, ...]:
    """Per-channel reduction axes for a weight leaf: everything except
    the trailing output-feature axis."""
    return tuple(range(x.ndim - 1))


def quantize_params(params: PyTree) -> PyTree:
    """Swap every floating matrix-or-higher param leaf for a per-channel
    int8 :class:`QTensor`; vectors (biases, norm scales) and integer
    leaves stay as-is — they are a rounding error of total bytes and
    precision-critical. The pytree *structure* above the leaves is
    unchanged, so param sharding-role trees still line up (the QTensor's
    int8 leaf keeps the original roles; its scale is replicated)."""

    def f(x):
        if (hasattr(x, "ndim") and x.ndim >= 2
                and jnp.issubdtype(x.dtype, jnp.floating)):
            return quantize(x, axis=_weight_axis(x))
        return x

    return jax.tree.map(f, params)


def dequantize_params(params: PyTree, dtype=None) -> PyTree:
    """Inverse of :func:`quantize_params` — called at the top of jitted
    step functions so int8 weights stay HBM-resident and the f32/bf16
    working copy only ever exists transiently inside the step."""
    return jax.tree.map(
        lambda x: dequantize(x, dtype) if is_qtensor(x) else x,
        params, is_leaf=is_qtensor)


def param_qdims(param_dims: PyTree) -> PyTree:
    """Sharding-role tree matching :func:`quantize_params` output: the
    int8 leaf keeps the param's roles, the scale — extent-1 on every
    reduced axis — is replicated."""

    def conv(d):
        if isinstance(d, tuple) and len(d) >= 2:
            return QTensor(q=d, scale=(None,) * len(d))
        return d

    is_dims = lambda x: isinstance(x, tuple) and not isinstance(x, QTensor)
    return jax.tree.map(conv, param_dims, is_leaf=is_dims)


# ---------------------------------------------------------------------------
# KV-cache quantisation: per-token scales over the head dimension
# ---------------------------------------------------------------------------

def quantize_kv(x: jax.Array) -> QTensor:
    """Per-token KV quantisation: ``x [..., G, D]`` → int8 with a
    ``[..., G, 1]`` scale (one scale per token per KV group)."""
    return quantize(x, axis=x.ndim - 1)


def kv_scale_bytes_per_elem(head_dim: int) -> float:
    """Extra bytes/element the f32 per-token scale adds to an int8 KV
    leaf (4 bytes amortised over one head's ``head_dim`` values)."""
    return 4.0 / max(int(head_dim), 1)


# ---------------------------------------------------------------------------
# the user / planner surface
# ---------------------------------------------------------------------------

_MODES = (None, "int8")


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """What gets quantised on the serving path.

    ``weights`` — ``"int8"`` stores params as per-channel int8 QTensors
    (HBM-resident; dequantised transiently inside the jitted step).
    ``kv`` — ``"int8"`` stores KV-cache rows as int8 with per-token f32
    scale leaves (``k_scale``/``v_scale``) that ride through splice,
    paging and disaggregation structurally.
    """

    weights: Optional[str] = None
    kv: Optional[str] = None

    def __post_init__(self):
        for name in ("weights", "kv"):
            v = getattr(self, name)
            if v not in _MODES:
                raise ValueError(f"QuantConfig.{name}={v!r}; known: {_MODES}")

    @property
    def enabled(self) -> bool:
        return self.weights is not None or self.kv is not None

    @property
    def quant_kv(self) -> bool:
        return self.kv is not None

    @property
    def quant_weights(self) -> bool:
        return self.weights is not None

    # --- planner bytes model -------------------------------------------
    def param_bytes_per_elem(self, default: float) -> float:
        """Serving-weight bytes/element under this config (int8 payload;
        the per-channel scale is ~4/fan_in bytes/elem — noise)."""
        return 1.0 if self.quant_weights else default

    def kv_bytes_per_elem(self, default: float, head_dim: int = 64) -> float:
        """KV-cache bytes/element: int8 payload + the amortised per-token
        scale (see :func:`kv_scale_bytes_per_elem`)."""
        if not self.quant_kv:
            return default
        return 1.0 + kv_scale_bytes_per_elem(head_dim)


#: canonical full-INT8 serving config
INT8_SERVE = QuantConfig(weights="int8", kv="int8")
