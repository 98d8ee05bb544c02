"""XFER — the paper's §4.3 technique as JAX shardings + collectives.

Baseline (paper Fig. 7 f/g): the *shared* tensor of a partition scheme is
replicated — every device re-reads all of it from its own memory (HBM).

XFER (paper Fig. 8): the shared tensor is *distributed* across the sharing
group; each device reads 1/P from HBM and receives the rest over the
inter-device links (ICI all-gather). For LM weights under DP/SP this is
ZeRO-3/FSDP-style weight gathering; the paper's tile-level double buffering
becomes a **one-layer-ahead weight prefetch** inside the scan
(:func:`scan_layers`), so the gather of layer *i+1* has no data dependence
on layer *i*'s compute and the XLA latency-hiding scheduler overlaps them.

All sharding decisions flow through :class:`ShardingCtx`, which turns
logical dim names into `PartitionSpec`s with divisibility checking, so the
same model code runs on a 1-device CPU test, a 256-chip pod, or a
multi-pod mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.planner import ShardingPlan

PyTree = Any


def _fits(size: int, axes: Sequence[str], axis_sizes: Dict[str, int]) -> Tuple[str, ...]:
    """Longest prefix of `axes` whose product divides `size`."""
    out = []
    prod = 1
    for a in axes:
        if size % (prod * axis_sizes[a]) == 0:
            out.append(a)
            prod *= axis_sizes[a]
        else:
            break
    return tuple(out)


@dataclasses.dataclass
class ShardingCtx:
    """Logical-dim → mesh-axis resolution for one plan.

    Logical dims:
      batch, seq         activation data dims (weight-shared partitions)
      tp                 IFM-shared partition (paper Pm): heads/ff/vocab/experts
      xfer               weight input-dim distribution (paper Fig. 8) — only
                         populated when plan.xfer is on
      ep                 expert dim
      none               explicit replication
    """

    mesh: Optional[Mesh]
    plan: ShardingPlan

    def __post_init__(self):
        self.axis_sizes = dict(self.plan.mesh_axes)
        self.roles: Dict[str, Tuple[str, ...]] = {
            "batch": self.plan.batch_axes,
            "seq": self.plan.seq_axes,
            # residual-stream sequence dim: SP over the tp axis as well
            # (Megatron-SP; keeps remat'd activations 1/tp per device)
            "sp": self.plan.seq_axes + tuple(
                a for a in self.plan.tp_axes if a not in self.plan.seq_axes),
            "tp": self.plan.tp_axes,
            "xfer": (self.plan.batch_axes + self.plan.seq_axes) if self.plan.xfer else (),
            # optimizer states always shard over the weight-sharing group
            # (ZeRO-1), independent of whether params do (XFER):
            "zero": self.plan.batch_axes + self.plan.seq_axes,
            "ep": self.plan.ep_axes,
            "none": (),
        }

    # ---- spec construction ----
    def spec(self, shape: Sequence[int], dims: Sequence[Optional[str]]) -> P:
        """PartitionSpec for `shape` with logical role per dim (None = replicated).

        Axes that do not divide the dim are dropped (degrade to replication),
        and an axis is used at most once across dims.
        """
        used: set = set()
        parts = []
        for size, role in zip(shape, dims):
            if role is None or role == "none":
                parts.append(None)
                continue
            cand = tuple(a for a in self.roles.get(role, ()) if a not in used)
            ax = _fits(size, cand, self.axis_sizes)
            used.update(ax)
            if not ax:
                parts.append(None)
            elif len(ax) == 1:
                parts.append(ax[0])
            else:
                parts.append(ax)
        return P(*parts)

    def sharding(self, shape: Sequence[int], dims: Sequence[Optional[str]]) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec(shape, dims))

    # ---- activation constraints (the paper's "keep data in-situ", §4.5) ----
    def constrain(self, x: jax.Array, *dims: Optional[str]) -> jax.Array:
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.spec(x.shape, dims)))

    # ---- XFER weight gather (Fig. 8: receive remote shards over ICI) ----
    def gather_params(self, params: PyTree, specs: PyTree) -> PyTree:
        """All-gather the xfer-distributed dims of a layer's params.

        `specs`: pytree of dim-role tuples matching `params`. The gathered
        form drops the "xfer" role (weights whole on each device of the
        sharing group) but keeps "tp"/"ep" (the IFM-shared partition stays).
        """
        if self.mesh is None or not self.plan.xfer:
            return params

        def gather(leaf, dims):
            g = tuple(None if d == "xfer" else d for d in dims)
            return jax.lax.with_sharding_constraint(
                leaf, NamedSharding(self.mesh, self.spec(leaf.shape, g)))

        return jax.tree.map(gather, params, specs,
                            is_leaf=lambda x: isinstance(x, jnp.ndarray))


def _is_dims(x) -> bool:
    return isinstance(x, tuple) and all(i is None or isinstance(i, str) for i in x)


def tree_shardings(ctx: ShardingCtx, value_tree: PyTree, dims_tree: PyTree) -> PyTree:
    """Resolve a parallel tree of logical-dim tuples into NamedShardings.

    The dims tree mirrors the value tree but holds role tuples at leaf
    positions (tuples are themselves pytrees, so the two trees are
    flattened independently with a custom is_leaf and zipped).
    """
    vals, treedef = jax.tree.flatten(value_tree)
    dims, _ = jax.tree.flatten(dims_tree, is_leaf=_is_dims)
    if len(vals) != len(dims):
        raise ValueError(f"dims tree mismatch: {len(vals)} values vs {len(dims)} dim tuples")
    out = []
    for v, d in zip(vals, dims):
        if not _is_dims(d):
            raise ValueError(f"bad dims entry {d!r}")
        shape = v.shape
        d = tuple(d)[: len(shape)] + (None,) * (len(shape) - len(d))
        out.append(NamedSharding(ctx.mesh, ctx.spec(shape, d)))
    return jax.tree.unflatten(treedef, out)


def null_ctx(plan: Optional[ShardingPlan] = None) -> ShardingCtx:
    """A no-mesh ctx for CPU smoke tests: every constraint is identity."""
    plan = plan or ShardingPlan(mesh_axes=(("data", 1), ("model", 1)),
                                batch_axes=("data",), tp_axes=("model",), xfer=False)
    return ShardingCtx(mesh=None, plan=plan)


# ---------------------------------------------------------------------------
# Layer scan with one-layer-ahead XFER prefetch (paper's double buffer,
# lifted from tile level to layer level — DESIGN.md §7.3).
# ---------------------------------------------------------------------------

def scan_layers(layer_fn: Callable[[PyTree, PyTree], PyTree],
                stacked_params: PyTree,
                x: PyTree,
                ctx: Optional[ShardingCtx] = None,
                specs: Optional[PyTree] = None,
                prefetch: bool = True,
                unroll: int = 1) -> PyTree:
    """Apply ``layer_fn`` over the leading (layer) axis of ``stacked_params``.

    With ``prefetch`` and an XFER plan, iteration *i* issues the all-gather
    for layer *i*'s weights while *computing layer i-1*: the two have no
    data dependence, so compute hides the ICI exchange (paper Fig. 3/6 —
    `Lat1 = max(tComp, tW_b2b)` instead of their sum).
    """
    leaves = jax.tree.leaves(stacked_params)
    num_layers = leaves[0].shape[0]

    use_prefetch = (prefetch and ctx is not None and ctx.mesh is not None
                    and ctx.plan.xfer and specs is not None and num_layers > 1)

    if not use_prefetch:
        def body(carry, p):
            return layer_fn(p, carry), None
        x, _ = jax.lax.scan(body, x, stacked_params, unroll=unroll)
        return x

    first = jax.tree.map(lambda a: a[0], stacked_params)
    rest = jax.tree.map(lambda a: a[1:], stacked_params)
    g0 = ctx.gather_params(first, specs)

    def body(carry, p_next):
        h, g = carry
        g_next = ctx.gather_params(p_next, specs)  # prefetch: no dep on h
        h = layer_fn(g, h)
        return (h, g_next), None

    (x, g_last), _ = jax.lax.scan(body, (x, g0), rest, unroll=unroll)
    return layer_fn(g_last, x)
