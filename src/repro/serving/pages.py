"""Paged KV cache: a device-resident block pool + prefix reuse.

The dense serving grid allocates one full-``max_len`` KV row per slot, so
device cache memory scales with ``slots × max_len`` regardless of how
many tokens are actually in flight. This module restructures that memory
the way the paper restructures accelerator traffic (§4: move load off
the saturated resource so capacity, not layout, sets the limit): KV
lives in a pool of fixed-size **pages** (``[kv_pages, page_size, G, D]``
per attention layer) and each slot holds an int32 **page table** row
mapping logical position blocks to physical pages. Capacity is then
*tokens in flight*, not slots × max_len — short requests stop paying for
the long tail, and identical prompt prefixes can share physical pages.

Layout
------
Every attention layer's head-major dense cache ``{k [B,G,T,D], v, pos,
count}`` becomes a token-major pool pair ``{"kp": [P, ps, G, D], "vp":
[P, ps, G, D]}``, so that a page holds whole token rows
(body-stack layers carry a leading repeats axis, mirroring
``models.lm.make_caches``). Page 0 is the reserved **null page**: decode
writes of inactive slots and masked splice writes land there, so the
fixed-shape step never needs a branch — null-page contents are garbage
by construction and never read unmasked. One page table
``[slots, ceil(max_len/page_size)]`` lives in ``DecodeState`` and is
shared by every layer (all layers page identically).

Allocation
----------
Page accounting is refcount-based (prefix sharing aliases pages across
slots). Two mirrored implementations, deliberately:

* :func:`pool_alloc` / :func:`pool_retain` / :func:`pool_release` —
  jitted pure functions over a :class:`PoolState` pytree, the
  device-resident form (donate-friendly, usable inside fused steps).
* :class:`PagePool` — the host mirror the scheduler actually drives:
  admission control needs the allocated page *ids* synchronously for
  Python control flow (grouping, exhaustion queueing, registry keys),
  and a device round-trip per admission would serialise the pipeline.
  The two are equivalence-tested against each other (tests/test_paging).

Exhaustion raises :class:`PagePoolExhausted` naming the waiting rids;
the scheduler catches it and degrades to queueing (requests wait for
pages to free), never crashes.

Prefix reuse
------------
:class:`PrefixRegistry` maps prompt prefixes to refcounted pages at
*token* granularity: every full-page boundary of an admitted prompt is
registered (``tokens[:j·ps] → pages[:j]``), plus one tail entry for the
full prompt (``tokens[:p] → (chain, frontier page, p mod ps)``). A later
prompt reuses the longest registered prefix: matched full pages are
**aliased** into its page table (refcount + 1, zero copy, zero compute),
and a partially-matched frontier page is **copied on write**
(:func:`copy_pages`) before the new request writes its own suffix into
it — the owner keeps decoding into the original. Prefill then computes
only the unmatched suffix against the gathered prefix KV
(:func:`gather_prefix`): admission cost scales with the *new* tokens.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

DEFAULT_PAGE_SIZE = 64

#: Families whose every cache leaf is a full-length attention KV row
#: (window 0): the only layout the page pool replaces. Hybrid (windowed
#: ring), pure-recurrent and enc-dec caches keep their existing layout.
PAGED_FAMILIES = ("dense", "moe", "vlm")


def paged_supported(arch) -> bool:
    return arch.family in PAGED_FAMILIES


def check_paged_supported(arch) -> None:
    if not paged_supported(arch):
        raise ValueError(
            f"paged KV cache supports all-attention families {PAGED_FAMILIES}, "
            f"not {arch.family!r} ({arch.name}): recurrent/windowed/enc-dec "
            f"caches keep their dense layout")


def num_pages_per_slot(max_len: int, page_size: int) -> int:
    """Page-table width: logical position blocks covering ``max_len``."""
    return -(-max_len // page_size)


def default_kv_pages(slots: int, max_len: int, page_size: int) -> int:
    """Dense-equivalent pool size (+1 null page): every slot can hold a
    full ``max_len`` sequence, so the default can never exhaust — callers
    opt into oversubscription by passing a smaller ``kv_pages``."""
    return slots * num_pages_per_slot(max_len, page_size) + 1


class PagePoolExhausted(RuntimeError):
    """An admission needed more free pages than the pool holds.

    ``waiting`` carries the rids whose admission is blocked — the
    scheduler re-queues them (FIFO) and retries as decode slots retire
    and release pages."""

    def __init__(self, msg: str, waiting: Sequence[int] = ()):
        super().__init__(msg)
        self.waiting = list(waiting)


# ---------------------------------------------------------------------------
# refcount accounting — jitted pure functions over a PoolState pytree
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PoolState:
    """Device-resident page accounting: ``refcount [P] int32``. Page 0
    (null) is born with refcount 1 so it is never allocated."""

    refcount: jax.Array

    @property
    def kv_pages(self) -> int:
        return self.refcount.shape[0]


jax.tree_util.register_dataclass(PoolState, data_fields=["refcount"],
                                 meta_fields=[])


def make_pool_state(kv_pages: int) -> PoolState:
    if kv_pages < 2:
        raise ValueError(f"kv_pages must be >= 2 (null page + one usable), "
                         f"got {kv_pages}")
    rc = jnp.zeros((kv_pages,), jnp.int32).at[0].set(1)
    return PoolState(refcount=rc)


@jax.jit
def pool_free_count(state: PoolState) -> jax.Array:
    return jnp.sum((state.refcount == 0).astype(jnp.int32))


def pool_alloc(state: PoolState, n: int) -> Tuple[PoolState, jax.Array]:
    """Take the ``n`` lowest-indexed free pages (refcount 0 → 1).

    Returns ``(state', pages [n] int32)``; positions past the free count
    return the null page 0 (callers check :func:`pool_free_count` — the
    pure function cannot raise). ``n`` is static (one jit per size).
    """

    @jax.jit
    def go(state):
        free = state.refcount == 0
        # stable order: lowest free indices first (argsort of ~free)
        order = jnp.argsort(jnp.where(free, jnp.arange(state.kv_pages),
                                      state.kv_pages).astype(jnp.int32))
        pages = order[:n].astype(jnp.int32)
        enough = jnp.cumsum(free.astype(jnp.int32))[-1] >= jnp.arange(1, n + 1)
        pages = jnp.where(enough, pages, 0)
        rc = state.refcount.at[pages].add(jnp.where(pages > 0, 1, 0))
        return PoolState(refcount=rc), pages

    return go(state)


@jax.jit
def pool_retain(state: PoolState, pages: jax.Array) -> PoolState:
    """refcount += 1 for each page id (null page 0 is a no-op)."""
    inc = jnp.where(pages > 0, 1, 0)
    return PoolState(refcount=state.refcount.at[pages].add(inc))


@jax.jit
def pool_release(state: PoolState, pages: jax.Array) -> PoolState:
    """refcount -= 1 for each page id, clamped at 0 (null page no-op)."""
    dec = jnp.where((pages > 0) & (state.refcount[pages] > 0), -1, 0)
    return PoolState(refcount=state.refcount.at[pages].add(dec))


# ---------------------------------------------------------------------------
# host mirror — the scheduler's synchronous allocator
# ---------------------------------------------------------------------------

class PagePool:
    """Host-side page accounting, bit-compatible with the ``pool_*``
    pure functions (same lowest-free-first policy; equivalence-tested).
    The scheduler needs page *ids* synchronously for admission control,
    so the authoritative refcounts live here and device state only ever
    receives the resulting page tables."""

    def __init__(self, kv_pages: int, page_size: int = DEFAULT_PAGE_SIZE):
        if kv_pages < 2:
            raise ValueError(f"kv_pages must be >= 2 (null page + one "
                             f"usable), got {kv_pages}")
        self.kv_pages = kv_pages
        self.page_size = page_size
        self.refcount = np.zeros((kv_pages,), np.int32)
        self.refcount[0] = 1  # null page: never allocated, never freed

    @property
    def free_pages(self) -> int:
        return int((self.refcount == 0).sum())

    @property
    def used_pages(self) -> int:
        return self.kv_pages - 1 - self.free_pages

    def alloc(self, n: int, waiting: Sequence[int] = ()) -> List[int]:
        """Allocate ``n`` pages (lowest free indices first) or raise
        :class:`PagePoolExhausted` naming the ``waiting`` rids."""
        free = np.flatnonzero(self.refcount == 0)
        if len(free) < n:
            raise PagePoolExhausted(
                f"page pool exhausted: need {n} pages, {len(free)} free "
                f"of {self.kv_pages - 1} (page_size={self.page_size}); "
                f"waiting rids={list(waiting)}", waiting)
        pages = free[:n].tolist()
        self.refcount[pages] = 1
        return pages

    def retain(self, pages: Sequence[int]) -> None:
        for p in pages:
            if p > 0:
                self.refcount[p] += 1

    def release(self, pages: Sequence[int]) -> None:
        for p in pages:
            if p <= 0:
                continue
            if self.refcount[p] <= 0:
                raise AssertionError(
                    f"double free of page {p} (refcount already 0)")
            self.refcount[p] -= 1


# ---------------------------------------------------------------------------
# pool construction (mirrors models.lm.make_caches structurally)
# ---------------------------------------------------------------------------

def _is_kv(node) -> bool:
    return isinstance(node, dict) and "k" in node and "v" in node

def _is_pool(node) -> bool:
    return isinstance(node, dict) and "kp" in node and "vp" in node


def _map_kv(tree, fn):
    """Apply ``fn`` to every dense KV-cache dict in a cache tree."""
    if _is_kv(tree):
        return fn(tree)
    if isinstance(tree, dict):
        return {k: _map_kv(v, fn) for k, v in tree.items()}
    raise ValueError(f"non-attention cache leaf in paged tree: {tree!r}")


#: dense-row leaf -> pool leaf names (scale pools only exist for int8 KV)
_POOL_NAMES = (("k", "kp"), ("v", "vp"), ("k_scale", "kps"), ("v_scale", "vps"))


def make_paged_caches(arch, kv_pages: int, page_size: int,
                      dtype=jnp.bfloat16, kv_quant: bool = False) -> PyTree:
    """Pool tree replacing ``REG.make_caches``: per attention layer
    ``{"kp": [P, ps, G, D], "vp": [P, ps, G, D]}`` (body layers keep the
    leading repeats axis). Page 0 is the null page. ``kv_quant=True``
    makes the payload pools int8 and adds per-token f32 scale pools
    ``{"kps": [P, ps, G, 1], "vps": ...}`` that page identically."""
    from repro.models import registry as REG
    check_paged_supported(arch)
    skeleton = jax.eval_shape(
        lambda: REG.make_caches(arch, 1, page_size, dtype, kv_quant=kv_quant))

    def conv(kv):
        out = {}
        for row_name, pool_name in _POOL_NAMES:
            if row_name not in kv:
                continue
            leaf = kv[row_name]  # [..., 1, G, ps, ·] → [..., P, ps, G, ·]
            g, ps, d = leaf.shape[-3:]
            shape = leaf.shape[:-4] + (kv_pages, ps, g, d)
            out[pool_name] = jnp.zeros(shape, leaf.dtype)
        return out

    return _map_kv(skeleton, conv)


def paged_cache_axes(arch, page_size: int, dtype=jnp.bfloat16,
                     kv_quant: bool = False) -> PyTree:
    """Per-leaf :class:`repro.models.registry.CacheAxes` for a pool tree,
    probed structurally like ``registry.cache_axes``: the axis that
    varies with ``kv_pages`` is the ``page`` axis; pool leaves have no
    batch-slot axis (the page table carries slot identity)."""
    from repro.models.registry import CacheAxes
    probes = [jax.eval_shape(
        lambda p=p: make_paged_caches(arch, p, page_size, dtype, kv_quant))
        for p in (4, 8)]

    def one(a, b):
        diff = [i for i, (x, y) in enumerate(zip(a.shape, b.shape)) if x != y]
        assert len(diff) == 1, (a.shape, b.shape)
        return CacheAxes(batch=None, length=None, page=diff[0])

    return jax.tree.map(one, *probes)


# ---------------------------------------------------------------------------
# device splice / gather / copy (jit-friendly pure functions)
# ---------------------------------------------------------------------------

def _pool_scatter(pool: jax.Array, rows: jax.Array, pages: jax.Array,
                  slots: jax.Array) -> jax.Array:
    """Scatter dense head-major ``rows [n, G, S, ·]`` into the token-major
    ``pool [(R,) P, ps, G, ·]`` at ``(pages, slots) [n, S]``. A leading
    repeats axis vmaps."""
    rows = rows.swapaxes(-3, -2)  # [(R,) n, S, G, ·]

    def one(p, r):
        return p.at[pages, slots].set(r.astype(p.dtype))
    if pool.ndim == 4:              # flat pool [P, ps, G, D], rows [n, S, G, D]
        if rows.ndim != 4:
            raise ValueError((pool.shape, rows.shape))
        return one(pool, rows)
    if rows.ndim == 4:              # body stack: [R, P, ps, G, D] vs [n,S,G,D]
        return jax.vmap(one, in_axes=(0, None))(pool, rows)
    return jax.vmap(one)(pool, rows)  # stacked rows too: [R, n, S, G, D]


def splice_pages(pools: PyTree, rows: PyTree, page_rows: jax.Array) -> PyTree:
    """Write batched dense prefill rows into the pool at the positions
    their ``pos`` leaves claim (``-1`` = invalid → routed to the null
    page). ``page_rows [n, M]`` are the slots' page-table rows; the
    bucketed row layout is unchanged — paging is purely a splice-target
    change, prefill compute stays dense."""

    def conv(pool_kv, row_kv):
        ps = pool_kv["kp"].shape[-3]
        pos = row_kv["pos"]
        pos = pos[0] if pos.ndim == 3 else pos  # body stack: pos same per repeat
        valid = pos >= 0
        logical = jnp.maximum(pos, 0)
        pages = jnp.take_along_axis(page_rows, logical // ps, axis=1)
        pages = jnp.where(valid, pages, 0)
        slots = logical % ps
        if "kps" in pool_kv and "k_scale" not in row_kv:
            # fp rows into an int8 pool (shared-prefix suffix prefill
            # returns raw fp rows): quantise at the scatter boundary with
            # the same per-token routine the dense fill uses, so the pool
            # bits match a full quantised prefill exactly
            from repro.quant import quantize_kv
            kq = quantize_kv(row_kv["k"])
            vq = quantize_kv(row_kv["v"])
            row_kv = dict(row_kv, k=kq.q, k_scale=kq.scale,
                          v=vq.q, v_scale=vq.scale)
        return {pool_name: _pool_scatter(pool_kv[pool_name], row_kv[row_name],
                                         pages, slots)
                for row_name, pool_name in _POOL_NAMES
                if pool_name in pool_kv}

    return _zip_kv(pools, rows, conv)


def _zip_kv(pools, rows, fn):
    if _is_pool(pools):
        return fn(pools, rows)
    if isinstance(pools, dict):
        return {k: _zip_kv(v, rows[k], fn) for k, v in pools.items()}
    raise ValueError(f"unexpected pool node: {pools!r}")


def gather_prefix(pools: PyTree, page_rows: jax.Array,
                  prefix_len: jax.Array) -> PyTree:
    """Per-layer shared-prefix KV for a compute-skip suffix prefill:
    gather the first ``K`` table entries' pages into dense
    ``{"pre_k": [n, K·ps, G, D], "pre_v", "pre_len": [n]}`` blocks the
    attention block concatenates ahead of the fresh suffix KV
    (``models.blocks.attn_apply``). ``page_rows`` is ``[n, K]`` —
    already truncated to the page span covering the prefix; entries at
    or beyond ``pre_len`` are garbage and masked by the block."""

    def conv(pool_kv, _):
        def one(p):
            g = p[page_rows]  # [n, K, ps, G, ·]
            return g.reshape(g.shape[0], -1, *g.shape[3:])

        def dense(name):
            # int8 pools dequantise here: the gathered prefix block feeds
            # straight into fp attention concat (blocks.attn_apply)
            g = one(pool_kv[name])
            if f"{name}s" in pool_kv:
                g = g.astype(jnp.float32) * one(pool_kv[f"{name}s"])
            return g

        kp = pool_kv["kp"]
        if kp.ndim == 5:  # body stack
            def dense_r(name):
                g = jax.vmap(one)(pool_kv[name])
                if f"{name}s" in pool_kv:
                    g = g.astype(jnp.float32) * jax.vmap(one)(pool_kv[f"{name}s"])
                return g
            return {"pre_k": dense_r("kp"), "pre_v": dense_r("vp"),
                    "pre_len": jnp.broadcast_to(
                        prefix_len, (kp.shape[0],) + prefix_len.shape)}
        return {"pre_k": dense("kp"), "pre_v": dense("vp"),
                "pre_len": prefix_len}

    return _zip_kv(pools, pools, conv)


def copy_pages(pools: PyTree, dst: jax.Array, src: jax.Array) -> PyTree:
    """Copy whole pages ``src [n] → dst [n]`` in every layer — the
    copy-on-write step for a partially-shared frontier page: the new
    request gets a private copy of the owner's page before writing its
    own suffix into it; the owner keeps decoding into the original."""

    def conv(pool_kv, _):
        def one(p):
            return p.at[dst].set(p[src])
        if pool_kv["kp"].ndim == 5:
            return {name: jax.vmap(one)(p) for name, p in pool_kv.items()}
        return {name: one(p) for name, p in pool_kv.items()}

    return _zip_kv(pools, pools, conv)


# ---------------------------------------------------------------------------
# prefix registry (host-side; token-granularity longest-prefix match)
# ---------------------------------------------------------------------------

class PrefixRegistry:
    """Prompt-prefix → physical-pages cache with refcounted aliasing.

    Entries (all host-side; pages pinned with one registry refcount):

    * ``full``: ``tokens[:j·ps] → (page_0..page_{j-1})`` for every full
      page boundary ``j`` of a registered prompt — aliasable as-is.
    * ``tail``: ``tokens[:p] → (chain, frontier page, p mod ps)`` for the
      full prompt when it ends mid-page — the frontier page is
      copy-on-write for a new sharer (the owner keeps appending to it).

    ``lookup`` returns the longest match at token granularity; ``cap``
    bounds both maps LRU-style (evicted entries drop their refcounts, so
    unreferenced pages return to the pool)."""

    def __init__(self, pool: PagePool, cap: int = 1024):
        self.pool = pool
        self.cap = cap
        self.full: "OrderedDict[bytes, Tuple[int, ...]]" = OrderedDict()
        self.tail: "OrderedDict[bytes, Tuple[Tuple[int, ...], int, int]]" = \
            OrderedDict()
        self.hits = 0
        self.misses = 0

    @staticmethod
    def _key(tokens: np.ndarray) -> bytes:
        return np.ascontiguousarray(tokens, dtype=np.int32).tobytes()

    def register(self, tokens: np.ndarray, pages: Sequence[int]) -> None:
        """Pin an admitted prompt's prefix pages. ``pages`` must cover
        ``ceil(len(tokens)/ps)`` entries of the slot's table."""
        ps = self.pool.page_size
        p = len(tokens)
        k_full, r = divmod(p, ps)
        for j in range(1, k_full + 1):
            self._put_full(self._key(tokens[:j * ps]), tuple(pages[:j]))
        if r and k_full < len(pages):
            self._put_tail(self._key(tokens[:p]),
                           (tuple(pages[:k_full]), int(pages[k_full]), r))

    def _put_full(self, key: bytes, chain: Tuple[int, ...]) -> None:
        if key in self.full:
            self.full.move_to_end(key)
            return
        self.pool.retain(chain)
        self.full[key] = chain
        self._evict()

    def _put_tail(self, key: bytes, entry) -> None:
        if key in self.tail:
            self.tail.move_to_end(key)
            return
        chain, frontier, _ = entry
        self.pool.retain(chain)
        self.pool.retain([frontier])
        self.tail[key] = entry
        self._evict()

    def _evict(self) -> None:
        while len(self.full) + len(self.tail) > self.cap:
            if self.full and (not self.tail or len(self.full) >= len(self.tail)):
                _, chain = self.full.popitem(last=False)
                self.pool.release(chain)
            else:
                _, (chain, frontier, _) = self.tail.popitem(last=False)
                self.pool.release(chain)
                self.pool.release([frontier])

    def evict_unreferenced(self) -> int:
        """Drop entries whose pages are only pinned by the registry —
        the exhaustion fallback that returns cold prefix pages to the
        pool. Nested prefixes of one prompt pin each other's pages, so
        "only the registry" means ``refcount == registry pin count``, not
        ``refcount == 1``. Returns the number of page pins released."""
        pins: Dict[int, int] = {}
        for chain in self.full.values():
            for p in chain:
                pins[p] = pins.get(p, 0) + 1
        for chain, frontier, _ in self.tail.values():
            for p in list(chain) + [frontier]:
                pins[p] = pins.get(p, 0) + 1
        freed = 0

        def try_evict(held):
            nonlocal freed
            if not all(self.pool.refcount[p] == pins.get(p, 0) for p in held):
                return False
            self.pool.release(held)
            for p in held:
                pins[p] -= 1
            freed += len(held)
            return True

        for key in list(self.full):
            if try_evict(list(self.full[key])):
                del self.full[key]
        for key in list(self.tail):
            chain, frontier, _ = self.tail[key]
            if try_evict(list(chain) + [frontier]):
                del self.tail[key]
        return freed

    def lookup(self, tokens: np.ndarray
               ) -> Tuple[int, Tuple[int, ...], Optional[int]]:
        """Longest registered prefix of ``tokens``, capped at
        ``len(tokens) - 1`` (at least one suffix token must run through
        prefill to produce the first logits).

        Returns ``(m, full_chain, frontier)``: ``m`` matched tokens, the
        aliasable full pages covering ``m // ps`` blocks, and — when
        ``m`` ends mid-page — the owner's frontier page to copy-on-write
        (``None`` on a clean page boundary). ``(0, (), None)`` on miss.
        """
        ps = self.pool.page_size
        q = len(tokens)
        best = (0, (), None)
        # tail entries first: they can match at token granularity
        for key, (chain, frontier, r) in self.tail.items():
            t_len = len(chain) * ps + r
            if t_len <= best[0] or t_len > q - 1:
                continue
            if key == self._key(tokens[:t_len]):
                best = (t_len, chain, frontier)
        # full-page boundaries, longest first
        j_max = (q - 1) // ps
        for j in range(j_max, 0, -1):
            if j * ps <= best[0]:
                break
            chain = self.full.get(self._key(tokens[:j * ps]))
            if chain is not None:
                best = (j * ps, chain, None)
                break
        if best[0]:
            self.hits += 1
            # LRU touch
            if best[2] is None:
                self.full.move_to_end(self._key(tokens[:best[0]]))
            else:
                self.tail.move_to_end(self._key(tokens[:best[0]]))
        else:
            self.misses += 1
        return best

    def clear(self) -> None:
        for chain in self.full.values():
            self.pool.release(chain)
        for chain, frontier, _ in self.tail.values():
            self.pool.release(chain)
            self.pool.release([frontier])
        self.full.clear()
        self.tail.clear()
