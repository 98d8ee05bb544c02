"""Compile for a described TPU v5e chip: the chip's compiler runs here,
with no chip attached, so a kernel or a step the chip would refuse fails
in tier-1 instead of on the chip.

Every compile in the repository's test suite that targets the chip lives
in this one file. The topology is described inside a module-scoped
fixture (never at import): only one process at a time may load the TPU
library, and pytest-xdist workers must all collect the same tests.
Nothing here runs; ``tpu_custom_call`` in the HLO shows that a Pallas
kernel was compiled by Mosaic rather than interpreted.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

import repro
from repro.configs.base import ShapeConfig

GIB = 1 << 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these compiles out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16", "int8"])
def test_paged_attention_compiles(one_chip, kv_int8):
    from repro.kernels.paged_attention import paged_attention
    b, h, g, d, ps, m, pages = 8, 16, 16, 64, 64, 32, 257
    pool_dt = jnp.int8 if kv_int8 else jnp.bfloat16
    args = [_sds(one_chip, (b, h, d), jnp.bfloat16),
            _sds(one_chip, (pages, ps, g, d), pool_dt),
            _sds(one_chip, (pages, ps, g, d), pool_dt),
            _sds(one_chip, (b, m), jnp.int32),
            _sds(one_chip, (b,), jnp.int32)]
    if kv_int8:
        scales = [_sds(one_chip, (pages, ps, g, 1))] * 2

        def fn(q, kp, vp, table, lens, ks, vs):
            return paged_attention(q, kp, vp, table, lens, k_scale=ks,
                                   v_scale=vs, interpret=False)
        args += scales
    else:
        def fn(q, kp, vp, table, lens):
            return paged_attention(q, kp, vp, table, lens, interpret=False)
    _assert_mosaic(_compile(fn, *args))


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention import flash_attention
    qkv = [_sds(one_chip, (16, 2048, 64), jnp.bfloat16)] * 3
    _assert_mosaic(_compile(
        lambda q, k, v: flash_attention(q, k, v, interpret=False), *qkv))


@pytest.mark.parametrize("kernel", ["xfer_matmul", "quant_matmul"])
def test_matmul_kernels_compile(one_chip, kernel):
    from repro.kernels.quant_matmul import quant_matmul
    from repro.kernels.xfer_matmul import xfer_matmul
    x = _sds(one_chip, (256, 1024), jnp.bfloat16)
    if kernel == "xfer_matmul":
        w = _sds(one_chip, (1024, 2816), jnp.bfloat16)
        c = _compile(lambda x, w: xfer_matmul(x, w, interpret=False), x, w)
    else:
        w = _sds(one_chip, (1024, 2816), jnp.int8)
        s = _sds(one_chip, (1, 2816))
        c = _compile(lambda x, w, s: quant_matmul(x, w, s, interpret=False),
                     x, w, s)
    _assert_mosaic(c)


def test_rglru_scan_compiles(one_chip):
    """recurrentgemma-2b's RG-LRU width (2560) over a 2048-token prefill."""
    from repro.kernels.rglru_scan import rglru_scan
    b, s, w = 2, 2048, 2560
    _assert_mosaic(_compile(
        lambda a, x, h: rglru_scan(a, x, h, interpret=False),
        _sds(one_chip, (b, s, w)), _sds(one_chip, (b, s, w)),
        _sds(one_chip, (b, w))))


def test_mlstm_chunkwise_compiles(one_chip):
    """xlstm-350m's mLSTM heads (4 heads of 512 = 2×d_model) over 2048."""
    from repro.kernels.mlstm_kernel import mlstm_chunkwise
    bh, s, d = 8, 2048, 512
    qkv = [_sds(one_chip, (bh, s, d))] * 3
    gates = [_sds(one_chip, (bh, s))] * 2
    _assert_mosaic(_compile(
        lambda q, k, v, i, f: mlstm_chunkwise(q, k, v, i, f,
                                              interpret=False),
        *qkv, *gates))


def _serve_step_compiled(topo, arch, slots, max_len, *, paged=False,
                         grid=(1, 1)):
    """The engine's fused decode step (sampling form) for described chips
    on a ``grid`` (data, model) mesh, with params, caches and state at
    the plan's shardings, caches and state donated."""
    from repro.core.xfer import tree_shardings
    from repro.models import registry as REG
    from repro.serving import pages as PG
    from repro.serving.sampler import GREEDY
    from repro.serving.state import decode_state_dims, make_decode_state

    n = grid[0] * grid[1]
    mesh = Mesh(np.array(topo.devices[:n]).reshape(grid), ("data", "model"))
    plan = repro.plan(arch, ShapeConfig("decode", max_len, slots, "decode"),
                      mesh=mesh)
    ctx = plan.ctx(mesh)
    dt = jnp.bfloat16
    params = jax.eval_shape(
        lambda: REG.init_params(arch, jax.random.PRNGKey(0), dt))
    table_len = None
    if paged:
        table_len = PG.num_pages_per_slot(max_len, PG.DEFAULT_PAGE_SIZE)
        caches = jax.eval_shape(lambda: PG.make_paged_caches(
            arch, PG.default_kv_pages(slots, max_len, PG.DEFAULT_PAGE_SIZE),
            PG.DEFAULT_PAGE_SIZE, dt))
        cache_sh = jax.tree.map(
            lambda _: jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            caches)
    else:
        caches = jax.eval_shape(
            lambda: REG.make_caches(arch, slots, max_len, dt))
        cache_sh = plan.cache_shardings(caches, mesh)
    state = jax.eval_shape(lambda: make_decode_state(slots, 0,
                                                     table_len=table_len))
    place = lambda tree, sh: jax.tree.map(  # noqa: E731
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, sh)
    step = REG.build_serve_step(arch, ctx, sampling=GREEDY, paged=paged)
    with mesh:
        return jax.jit(step, donate_argnums=(1, 2)).lower(
            place(params, plan.param_shardings(params, mesh)),
            place(caches, cache_sh),
            place(state, tree_shardings(ctx, state, decode_state_dims(
                paged=paged)))).compile()


def test_qwen_serve_step_fits_one_chip(topo):
    """qwen1.5-0.5b at published widths, 8 slots × 2048: the main path
    of ``chip_smoke.py`` compiles for one v5e and fits its 16 GiB."""
    compiled = _serve_step_compiled(topo, repro.get_arch("qwen1.5-0.5b"),
                                    slots=8, max_len=2048)
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.alias_size_in_bytes > GIB  # the donated KV grid is aliased
    assert live < 16 * GIB, f"{live / GIB:.2f} GiB"


def _grid_shaped_ops(hlo: str, shape: str):
    """(opcode, fusion kind) of every instruction, in any computation,
    whose result has exactly ``shape``; operands and plumbing left out."""
    inst = re.compile(r"^\s*(?:ROOT )?%\S+ = " + re.escape(shape)
                      + r"\{[^}]*\} ([\w-]+)\(")
    ops = []
    for line in hlo.splitlines():
        m = inst.match(line)
        if m and m.group(1) not in ("parameter", "get-tuple-element",
                                    "tuple", "bitcast"):
            kind = re.search(r"kind=(k\w+)", line)
            ops.append((m.group(1), kind.group(1) if kind else None))
    return ops


def _unfused_ops(hlo: str):
    """(opcode, fusion kind, result shapes) of every instruction in a
    computation that no fusion calls (the entry, loop bodies): the ops
    that read and write HBM. A fusion's own instructions are left out."""
    fused = set(re.findall(r" fusion\(.*?calls=%([\w.-]+)", hlo))
    inst = re.compile(r"^\s*(?:ROOT )?%\S+ = (.+?) ([\w-]+)\(")
    ops, inside = [], False
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.-]+) .*\{$", line)
        if head:
            inside = head.group(1) not in fused
            continue
        m = inst.match(line) if inside else None
        if m:
            kind = re.search(r"kind=(k\w+)", line)
            shapes = [tuple(int(x) for x in dims.split(",") if x)
                      for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(1))]
            ops.append((m.group(2), kind.group(1) if kind else None, shapes))
    return ops


def _yi9b_l16():
    return dataclasses.replace(repro.get_arch("yi-9b"), num_layers=16)


def test_yi9b_serve_step_writes_kv_grid_in_place(topo):
    """Yi-9B at published widths (d_model 4096, 32/4 heads of 128, d_ff
    11008, vocabulary 64000), 16 layers, 32 slots × 4096: the benchmark's
    ``yi-9b-l16`` serve step. Decode carries the stacked head-major K/V
    grid ``bf16[16,32,4,4096,128]`` (2 × 2.0 GiB) through the layer scan
    and scatters one token row per layer into it.

    Before (grid scanned as per-layer inputs and outputs): 4.51 GiB of
    temporaries, and grid-sized ``copy`` ×2, ``AllocateBuffer`` ×2 and
    ``dynamic-update-slice`` loop fusions ×2. After: the only grid-sized
    results are the two in-place scatter fusions. The bound, one layer's
    K and V slabs (0.25 GiB), also refuses carrying the grid but writing
    each layer's whole slab back (0.38 GiB)."""
    arch = _yi9b_l16()
    slots, max_len = 32, 4096
    compiled = _serve_step_compiled(topo, arch, slots=slots, max_len=max_len)
    mem = compiled.memory_analysis()
    shape = (arch.num_layers, slots, arch.num_kv_heads, max_len, arch.head_dim)
    grid_bytes = 2 * int(np.prod(shape)) * 2  # K and V, bf16
    assert mem.alias_size_in_bytes >= grid_bytes  # the donated grid
    assert mem.temp_size_in_bytes < grid_bytes / arch.num_layers, (
        f"temp {mem.temp_size_in_bytes / GIB:.3f} GiB")
    ops = _grid_shaped_ops(compiled.as_text(),
                           "bf16[" + ",".join(map(str, shape)) + "]")
    assert ops and all(op == "scatter" or (op == "fusion" and kind == "kCustom")
                       for op, kind in ops), ops


@pytest.mark.parametrize("config", ["yi-9b-l16", "yi-9b-2x2"])
def test_yi9b_serve_step_reads_kv_slab_in_place(topo, config):
    """Decode attention reads layer ``i``'s K and V slab where it lies in
    the carried head-major grid: the layer's ``dynamic-slice`` fuses into
    the two attention dot fusions, as the weights' slices do.

    With the grid stored token-major ``[L, B, t, G, D]`` the chip's
    compiler copied each layer's slab out and relaid it to the
    ``(b, g, t, d)`` order of the dots: a ``constant_dynamic-slice``
    fusion and a ``copy`` per slab, 128 MiB of temporaries on one chip
    (``yi-9b-l16``), 64 MiB per chip on the 2x2 host (``yi-9b-2x2``).
    Storing it head-major, with the token written head by head, leaves
    no such op and (almost) no temporaries. The bound is a sixteenth of
    one layer's K slab on one chip; no unfused ``copy``, ``transpose``,
    ``dynamic-slice`` or fusion may have a slab's element count, in any
    order."""
    if config == "yi-9b-l16":
        arch, slots, max_len, grid = _yi9b_l16(), 32, 4096, (1, 1)
    else:
        arch, slots, max_len, grid = repro.get_arch("yi-9b"), 128, 2048, (2, 2)
    chips = grid[0] * grid[1]
    compiled = _serve_step_compiled(topo, arch, slots=slots, max_len=max_len,
                                    grid=grid)
    slab = slots * arch.num_kv_heads * (max_len // chips) * arch.head_dim
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < slab * 2 / 16, (
        f"temp {mem.temp_size_in_bytes / (1 << 20):.2f} MiB")
    slab_ops = [(op, kind, shapes)
                for op, kind, shapes in _unfused_ops(compiled.as_text())
                if op in ("copy", "transpose", "dynamic-slice", "fusion")
                and any(int(np.prod(sh)) == slab for sh in shapes)]
    assert not slab_ops, slab_ops


def test_yi9b_whole_serve_step_fits_a_2x2_host(topo):
    """Yi-9B whole (48 layers at published widths), 128 slots × 2048: the
    benchmark's ``yi-9b-2x2`` serve step on a described v5e 2x2, at the
    plan's shardings (4-way tensor parallel over the data × model grid
    ``repro.plan`` fits to four devices, the K/V grid split by sequence).

    10.87 GB of arguments per device (the donated grid 6.46 GB of them).
    Temporaries: 0.068 GB while the grid was stored token-major (each
    layer's K/V slab relaid for the attention dots), none since it is
    head-major. Per layer, 5 all-reduces (the two
    row-parallel outputs ``bf16[128,1,4096]``; flash-decoding's merge,
    ``f32[128,1,32,128]`` and two ``f32[128,1,32,1]``) and 6 all-gathers
    (the new K/V rows ``bf16[128,4,128]`` ×4, the queries
    ``bf16[128,1,32,128]``, one ``bf16[128,512]``): 241 all-reduces and
    290 all-gathers per step with the embedding's and the state's. Every
    one is activation-sized; the 16 MB bound refuses any gather of a
    weight or of the grid, and the bound of 12 per layer makes a change
    that adds an exchange to every layer say so here."""
    from repro.launch.hlo_analysis import collective_stats
    arch = repro.get_arch("yi-9b")
    assert arch.num_layers == 48
    slots, max_len, chips = 128, 2048, 4
    compiled = _serve_step_compiled(topo, arch, slots=slots, max_len=max_len,
                                    grid=(2, 2))
    mem = compiled.memory_analysis()
    per_device = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert per_device < 15.75e9, f"{per_device / 1e9:.2f} GB"
    # a sixteenth of one layer's K slab on one device
    slab = slots * max_len * arch.num_kv_heads * arch.head_dim * 2 // chips
    assert mem.temp_size_in_bytes < slab / 16, (
        f"temp {mem.temp_size_in_bytes / 1e9:.3f} GB")
    stats = collective_stats(compiled)
    assert max(v["max_bytes"] for v in stats.values()) <= 16e6, stats
    per_layer = sum(v["count"] for v in stats.values()) / arch.num_layers
    assert 0 < per_layer <= 12, stats


def test_paged_serve_step_emits_kernel_on_tpu(topo, monkeypatch):
    """With the paged kernel selected, the platform choice in
    ``kernels/ops.py`` compiles it by Mosaic on TPU (not interpreted)."""
    from repro.kernels import ops
    from repro.models import blocks
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(blocks, "_PAGED_ATTN_IMPL", "kernel")
    arch = dataclasses.replace(repro.get_arch("qwen1.5-0.5b"), num_layers=2)
    compiled = _serve_step_compiled(topo, arch, slots=8, max_len=2048,
                                    paged=True)
    _assert_mosaic(compiled)
