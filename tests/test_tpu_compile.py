"""Compile-only check against a TPU v5e topology description — no chip.

``jax.experimental.topologies.get_topology_desc(platform="tpu", ...)`` hands
back TPU devices backed by the installed libtpu's REAL compilers: lowering a
function for them runs Pallas → Mosaic and XLA:TPU end to end and refuses an
oversize VMEM scratch or an over-HBM program exactly as a chip would. What
it cannot do is execute — numerics on silicon are ``chip_smoke.py``'s job.

The functions are lowered with their DEFAULT arguments from this CPU
process: the kernel/reference choice follows the lowering target
(``ops/attention.py::_dispatch``), so what compiles here is what ``serve``
runs on the chip, at Qwen2-7B's head geometry (Hq 28, Hkv 4 → G 7, D 128,
page 16, chunk 512).
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from radixmesh_tpu.cache import kv_pool
from radixmesh_tpu.models import get_config
from radixmesh_tpu.models.llama import (
    _PREC,
    _init_params,
    _wmm,
    decode_step,
    prefill_chunk_paged,
    prefill_forward,
)
from radixmesh_tpu.ops.attention import paged_decode_attention

B, HQ, HKV, D, PAGE, CHUNK, MAXP, L = 8, 28, 4, 128, 16, 512, 64, 2


@pytest.fixture(scope="module")
def v5e():
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    assert topo.devices[0].platform == "tpu"
    # A compile-only client cannot load executables back, so the suite's
    # persistent compile cache would only collect dead TPU entries.
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cached)


def _sd(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding
    )


def _copies_shaped_like(compiled, *shapes) -> list[str]:
    """The ``copy`` operations of the optimized program whose result has
    one of ``shapes``, flat or viewed as pages: a relayout of the pool."""
    want = set()
    for shape in shapes:
        slot_axis = len(shape) - 2
        paged = (shape[:slot_axis] + (shape[slot_axis] // PAGE, PAGE)
                 + shape[slot_axis + 1:])
        want |= {",".join(map(str, shape)), ",".join(map(str, paged))}
    found = re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", compiled.as_text())
    return [dims for dims in found if dims in want]


def test_same_functions_take_the_reference_on_cpu():
    """The other side of the lowering-target rule: lowered for this
    process's CPU, the very same call holds no Mosaic kernel."""
    z = jnp.zeros
    compiled = jax.jit(
        lambda q, k, v, kv, sl, pt, ln: paged_decode_attention(
            q, k, v, kv, sl, pt, ln, 0
        )
    ).lower(
        z((2, HQ, D), jnp.bfloat16), z((2, HKV, D), jnp.bfloat16),
        z((2, HKV, D), jnp.bfloat16),
        z((2, L, HKV, 8, PAGE, D), jnp.bfloat16), z((2,), jnp.int32),
        z((2, 4), jnp.int32), z((2,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" not in compiled.as_text()


def test_decode_step_and_prefill_wave_fit_one_v5e(v5e):
    """``decode_step`` and the default 8 x 512 prefill wave at Qwen2-7B
    widths, W8A16, all 28 layers, the 65 536-slot pool ``chip_smoke.py``
    serves with: Mosaic accepts the fused-decode and chunk kernels inside
    them, XLA:TPU accepts both programs for a 16 GB chip (it refuses one
    over HBM), the pool is aliased through, decode allocates no
    temporaries, and the wave returns one logits row per sequence."""
    sd = _sd(v5e)
    cfg = get_config("qwen2-7b")
    params = jax.tree.map(
        lambda x: sd(x.shape, x.dtype),
        jax.eval_shape(
            lambda k: _init_params(k, cfg, "int8", None),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        ),
    )
    pool = sd((2, cfg.n_layers, cfg.n_kv_heads, 65536, cfg.head_dim), cfg.dtype)
    gib = 2.0**30

    compiled = decode_step.lower(
        params, cfg, sd((B,), jnp.int32), pool, sd((B,), jnp.int32),
        sd((B, MAXP), jnp.int32), sd((B,), jnp.int32), PAGE,
    ).compile()
    mem = compiled.memory_analysis()
    assert "tpu_custom_call" in compiled.as_text()
    assert mem.alias_size_in_bytes / gib == pytest.approx(3.5)  # the pool
    assert mem.argument_size_in_bytes / gib == pytest.approx(10.6, abs=0.05)
    assert mem.temp_size_in_bytes / gib < 0.05

    compiled = prefill_chunk_paged.lower(
        params, cfg, sd((B, CHUNK), jnp.int32), sd((B, CHUNK), jnp.int32),
        pool, sd((B, CHUNK), jnp.int32), sd((B, 128), jnp.int32),
        sd((B,), jnp.int32), page_size=PAGE, logits_at=sd((B,), jnp.int32),
    ).compile()
    mem = compiled.memory_analysis()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info[0].shape == (B, 1, cfg.vocab_size)
    assert mem.alias_size_in_bytes / gib == pytest.approx(3.5)
    live = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    ) / gib
    assert live < 15.75, f"prefill wave needs {live:.2f} GiB"
    # Its rows enter the pool by whole pages: no copy of the pool (3.5 GiB
    # each way round a scatter on the slot axis, until PR 34).
    assert not _copies_shaped_like(compiled, pool.shape)
    assert mem.temp_size_in_bytes / gib < 3.5 / 4


def test_mellum2_period_compiles_for_one_v5e(v5e):
    """One period of Mellum2-12B-A2.5B (sliding x3 + full) at published
    widths, bf16: Mosaic accepts the grouped expert product (64 experts of
    2304 x 896 as one block each) and the fused decode and chunk kernels
    WITH a window start (32 q / 4 kv heads, G = 8), under both call-site
    names; the pool is aliased through; the step returns the expert ids
    and the counters."""
    from radixmesh_tpu.models.mellum2 import mellum2_12b_a2_5b

    sd = _sd(v5e)
    cfg = mellum2_12b_a2_5b(4)
    params = jax.tree.map(
        lambda x: sd(x.shape, x.dtype),
        jax.eval_shape(
            lambda k: _init_params(k, cfg, None, None),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        ),
    )
    pool = sd((2, cfg.n_layers, cfg.n_kv_heads, 65536, cfg.head_dim), cfg.dtype)
    rows = 32
    compiled = decode_step.lower(
        params, cfg, sd((rows,), jnp.int32), pool, sd((rows,), jnp.int32),
        sd((rows, 1024), jnp.int32), sd((rows,), jnp.int32), PAGE,
    ).compile()
    text = compiled.as_text()
    assert "radixmesh_moe_gmm_decode" in text
    assert "radixmesh_decode_fused_mh" in text
    logits, _, aux = compiled.out_info
    assert logits.shape == (rows, cfg.vocab_size)
    assert aux["expert_ids"].shape == (4, rows, 1, 8)
    assert aux["moe_stats"].shape == (2, 4, 2)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * 4 * 4 * 65536 * 128 * 2  # the pool
    assert mem.temp_size_in_bytes / 2.0**30 < 0.3

    compiled = prefill_chunk_paged.lower(
        params, cfg, sd((B, CHUNK), jnp.int32), sd((B, CHUNK), jnp.int32),
        pool, sd((B, CHUNK), jnp.int32), sd((B, 512), jnp.int32),
        sd((B,), jnp.int32), page_size=PAGE, logits_at=sd((B,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert "radixmesh_moe_gmm_prefill" in text and "radixmesh_chunk_attn" in text
    assert compiled.out_info[0].shape == (B, 1, cfg.vocab_size)
    assert not _copies_shaped_like(compiled, pool.shape)


# The chunk kernel at the shapes the cells' chunk programs hand it: rows x
# chunk, q / kv heads of the pool row, page-table width, window, scale,
# the pool's dtype.
CHUNK_LAUNCHES = {
    # a lone chat turn: one row at the engine's one table width
    "qwen2-1x256": (1, 256, 28, 4, 512, False, None, jnp.bfloat16),
    "qwen2-8x512": (8, 512, 28, 4, 128, False, None, jnp.bfloat16),
    # ``--kv-quant int8``: float32 q, chunk K/V and upcast blocks, and the
    # table's prepared scale rows, beside the int8 double buffer
    "qwen2-8x512-int8-pool": (8, 512, 28, 4, 128, False, None, jnp.int8),
    # a window and a full layer share the executable: the window is traced
    "mellum2-8x512-window": (8, 512, 32, 4, 512, True, None, jnp.bfloat16),
    # two heads of 64 to a pool row, the logits scaled for 64; every chunk
    # program of the agent cell has the 2 048-page table
    "lfm2-1x512-one-width": (1, 512, 32, 4, 2048, False, 0.125, jnp.bfloat16),
    # the window group's launch: 64 rows x 64 tokens x 1 024 pages, ten
    # wide heads (a 512-token block of them is over the 4 MiB a block gets)
    "phi4-64x64-window-group": (64, 64, 40, 10, 1024, True, 0.125,
                                jnp.bfloat16),
    # the speculative verify chunk: a few tokens a row
    "qwen2-8x4-verify": (8, 4, 28, 4, 128, False, None, jnp.bfloat16),
}


@pytest.mark.parametrize("launch", list(CHUNK_LAUNCHES))
def test_chunk_kernel_lowers_at_the_cells_launch_shapes(v5e, launch):
    """``paged_chunk_attention`` lowered for the v5e takes the Pallas chunk
    kernel (one ``radixmesh_chunk_attn``), and Mosaic accepts it inside the
    16 MiB of VMEM a kernel gets without asking (it refuses a kernel over
    that; the wrapper asks for no more: ``_CHUNK_VMEM_BUDGET``) — heads a
    program, block and query block derived from these shapes alone."""
    from radixmesh_tpu.ops import paged_attention as pa
    from radixmesh_tpu.ops.attention import paged_chunk_attention

    rows, chunk, hq, hkv, pages, windowed, scale, pool = CHUNK_LAUNCHES[launch]
    quantized = pool == jnp.int8
    sd = _sd(v5e)
    bf16 = lambda *shape: sd(shape, jnp.bfloat16)  # noqa: E731
    ints = lambda *shape: sd(shape, jnp.int32)  # noqa: E731
    heads, ppb, cblk = pa._chunk_geometry(
        chunk, hq // hkv, hkv, PAGE, D, pool, jnp.bfloat16, pages,
        None, None, quantized)
    assert heads == hkv and chunk % cblk == 0
    assert ppb * PAGE == (256 if hkv == 10 else 512)

    def attend(q, k, v, kv, pt, pos, prior, kvlen, **kw):
        return paged_chunk_attention(
            q, k, v, kv, pt, pos, prior, kvlen, 1, scale=scale, **kw)

    more = {"window": ints()} if windowed else {}
    if quantized:
        more["kv_scales"] = sd((2, L, hkv, 4096, PAGE), jnp.float32)
    compiled = jax.jit(attend).lower(
        bf16(rows, chunk, hq, D), bf16(rows, chunk, hkv, D),
        bf16(rows, chunk, hkv, D), sd((2, L, hkv, 4096, PAGE, D), pool),
        ints(rows, pages), ints(rows, chunk), ints(rows), ints(rows), **more,
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "radixmesh_chunk_attn" in text
    call, = (line for line in text.splitlines()
             if "tpu_custom_call" in line and "radixmesh_chunk_attn" in line)
    assert '"scoped_memory_configs":[]' in call  # no VMEM asked for


def test_glm47_decode_reads_shared_pages_in_one_pass_at_the_cells_bucket(v5e):
    """GLM-4.7-Flash ``decode_step`` at the docs cell's bucket (32 rows x
    2 048 pages, the served pool's slots): both passes of the latent decode
    kernel lower for the chip under the one name the trace readers key on,
    the pool aliased through. What the rows share is DATA: the groups are
    an abstract ``[rows // 2, 9]`` argument here, so a group count or a
    shared length that reached a static argument or a shape would fail the
    trace; and the host gives lines of that one shape whatever the page
    table holds, so every launch of a row bucket runs this program."""
    import numpy as np

    from radixmesh_tpu.ops.mla_attention import decode_groups

    sd = _sd(v5e)
    rows, pages = 32, 2048
    cfg = get_config("glm-4.7-flash", n_layers=2, vocab_size=4096)
    params = jax.tree.map(
        lambda x: sd(x.shape, x.dtype),
        jax.eval_shape(lambda k: _init_params(k, cfg, None, None),
                       jax.ShapeDtypeStruct((2,), jnp.uint32)))
    pool_shape = cfg.kv_pool_shape(196608)
    table = np.arange(rows * pages, dtype=np.int32).reshape(rows, pages)
    lengths = np.full(rows, 20000, np.int32)
    nothing, none = decode_groups(table, lengths, range(rows), PAGE)
    table[:13, :1024] = table[0, :1024]  # 13 rows on one 16 k-token document
    table[20:22, :64] = table[20, :64]  # two on another kilotoken
    shared, some = decode_groups(table, lengths, range(rows), PAGE)
    assert (none["groups"], some["groups"], some["passes"]) == (0, 2, 3)
    assert some["shared_tokens"] == 12 * 16384 + 1024
    assert nothing.shape == shared.shape == (rows // 2, 9)
    assert nothing.dtype == shared.dtype == np.int32
    compiled = decode_step.lower(
        params, cfg, sd((rows,), jnp.int32), sd(pool_shape, cfg.dtype),
        sd((rows,), jnp.int32), sd((rows, pages), jnp.int32),
        sd((rows,), jnp.int32), PAGE,
        decode_groups=sd(shared.shape, jnp.int32),
    ).compile()
    calls = re.findall(r"custom_call_target=\"tpu_custom_call\"[^\n]*"
                       r"radixmesh_mla_decode", compiled.as_text())
    kernels = set(re.findall(r"radixmesh_(?!tpu)[a-z_]+[a-z]", compiled.as_text()))
    # the dense first layer and the scanned sparse ones: two passes each
    assert len(calls) == 4, calls
    assert kernels == {"radixmesh_mla_decode", "radixmesh_moe_gmm_decode"}
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= np.prod(pool_shape) * 2


# The served pools (benchmarks/cells/*.json): Qwen2-7B, Mellum2 (12 layers),
# GLM-4.7-Flash (8 layers, one latent plane), GLM-5.2 (7 layers) and its
# index plane.
POOLS = {
    "qwen2": (2, 28, 4, 65536, 128),
    "mellum2": (2, 12, 4, 65536, 128),
    "glm47": (1, 8, 1, 196608, 640),
    "glm52": (1, 7, 1, 221184, 640),
}
INDEX_PLANE = (2, 221184, 128)


@pytest.mark.parametrize("rows", [512, 4096])
@pytest.mark.parametrize("what", ["write", "gather"])
@pytest.mark.parametrize("name", list(POOLS))
def test_rows_enter_and_leave_the_pool_without_its_relayout(v5e, name, what, rows):
    """``PagedKVPool.write`` / ``.gather`` of one run of rows: XLA:TPU
    scatters into (gathers from) the slot axis only after copying the
    WHOLE pool into another layout — whole pages move without. No ``copy``
    of the pool's shape, no temporary of its size, the write in place."""
    sd = _sd(v5e)
    shape = POOLS[name]
    pool = sd(shape, jnp.bfloat16)
    size = 2 * int(jnp.prod(jnp.asarray(shape, jnp.float32)))
    slots = sd((1, rows), jnp.int32)
    if what == "write":
        new = sd(shape[:3] + (rows, shape[4]), jnp.bfloat16)
        compiled = kv_pool._scatter_kv.lower(pool, slots, new, PAGE).compile()
        assert compiled.memory_analysis().alias_size_in_bytes == size
    else:
        compiled = kv_pool._gather_kv.lower(pool, slots, PAGE).compile()
        assert compiled.out_info.shape == (
            shape[0], shape[1], rows, shape[2], shape[4])
    assert not _copies_shaped_like(compiled, shape)
    assert compiled.memory_analysis().temp_size_in_bytes < size / 4


@pytest.mark.parametrize("rows", [512, 4096])
def test_index_keys_enter_their_plane_without_its_relayout(v5e, rows):
    sd = _sd(v5e)
    plane = sd(INDEX_PLANE, jnp.bfloat16)
    size = 2 * INDEX_PLANE[0] * INDEX_PLANE[1] * INDEX_PLANE[2]
    compiled = kv_pool._scatter_index.lower(
        plane, sd((1, rows), jnp.int32),
        sd((INDEX_PLANE[0], rows, INDEX_PLANE[2]), jnp.bfloat16), PAGE,
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == size
    assert mem.temp_size_in_bytes < size / 4
    assert not _copies_shaped_like(compiled, INDEX_PLANE)


def test_a_scatter_on_the_slot_axis_still_relayouts_the_pool(v5e):
    """The other side: the per-token scatter that any set of slots takes
    (and every write took until PR 34) holds two copies of the pool. If
    this stops failing, the pages are no longer needed."""
    sd = _sd(v5e)
    shape = POOLS["mellum2"]
    compiled = kv_pool._scatter_kv.lower(
        sd(shape, jnp.bfloat16), sd((512,), jnp.int32),
        sd(shape[:3] + (512, shape[4]), jnp.bfloat16), PAGE,
    ).compile()
    assert len(_copies_shaped_like(compiled, shape)) == 2


# ---------------------------------------------------------------------------
# the layer scan reads the attention projections where they lie (PR 40)
# ---------------------------------------------------------------------------

_HLO_DTYPE = {"int8": "s8", "bfloat16": "bf16"}


def _standalone_weight_ops(compiled, head_dim: int, weights) -> list[str]:
    """The ``copy`` operations and the fusions of the optimized program
    that stand ALONE — in the entry, a loop body or a branch, not inside
    another fusion — and whose result is one of ``weights`` (a layer's
    slice ``[in, out]`` of a stacked projection, by shape and dtype): flat,
    transposed, or viewed by head. A product that reads its stack at the
    layer's index holds the slice INSIDE its own fusion and returns
    activations; a stand-alone one is the slice written out to a buffer
    of its own (``constant_dynamic-slice_fusion.N``), a ``copy`` its
    relayout. The callers keep their token counts off every weight's
    dimensions, so no activation has such a shape."""
    want = set()
    for w in weights:
        dtype = _HLO_DTYPE[str(w.dtype)]
        a, b = w.shape[-2:]
        views = {(a, b), (b, a)}
        for x, y in ((a, b), (b, a)):
            if y % head_dim == 0:
                views |= {(x, y // head_dim, head_dim), (y // head_dim, head_dim, x)}
        for v in views:
            dims = ",".join(map(str, v))
            want |= {(dtype, dims), (dtype, "1," + dims)}
    text = compiled.as_text()
    fused = set(re.findall(r"calls=(%[\w.\-]+)", text))
    found, inside = [], None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(", line)
        if head:
            inside = head.group(1)
        op = re.match(r"\s+(?:ROOT )?(\S+) = (\w+)\[([\d,]+)\]\S* (copy|fusion)\(", line)
        if op and inside not in fused and (op.group(2), op.group(3)) in want:
            found.append(f"{op.group(1)} {op.group(2)}[{op.group(3)}]")
    return found


def _served(name):
    """``(cfg, weight_quant, rows of the chunk wave)`` of a served GQA
    configuration at published widths: Qwen2-7B W8A16 (bias), one period
    of Mellum2 (bf16) and three of LFM2 (bf16, heads of 64 with their
    norms, a scanned run of two periods between unscanned layers). The
    wave's tokens (rows x 512) and the dense prefill's 1 024 equal no
    dimension of the configuration's projections."""
    if name == "qwen2":
        return get_config("qwen2-7b"), "int8", 8
    if name == "mellum2":
        from radixmesh_tpu.models.mellum2 import mellum2_12b_a2_5b

        return mellum2_12b_a2_5b(4), None, 4
    period = ("conv", "conv", "full_attention", "conv")
    return get_config("lfm2-24b-a2b-ep8").replace(
        n_layers=12, layer_types=period * 3, vocab_size=4096), None, 8


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk_paged",
                                     "prefill_forward"])
@pytest.mark.parametrize("name", ["qwen2", "mellum2", "lfm2"])
def test_projections_are_read_where_they_lie_in_the_stack(v5e, name, program):
    """No entry point's layer scan slices ``wq`` / ``wk`` / ``wv`` / ``wo``
    out of their stacks into buffers of their own, nor copies a slice
    transposed: every projection product takes the stack and the layer's
    index, as the FFN's do (until PR 40: 29 MB a layer sliced, 16.5 MB of
    it copied again, before the products of every Qwen2-7B decode step)."""
    sd = _sd(v5e)
    cfg, quant, wave_rows = _served(name)
    shapes = jax.eval_shape(
        lambda k: _init_params(k, cfg, quant, None),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    params = jax.tree.map(lambda x: sd(x.shape, x.dtype), shapes)
    stack = shapes["attn" if cfg.has_conv else "layers"]
    weights = [stack[w] for w in ("wq", "wk", "wv", "wo")]
    ints = lambda *shape: sd(shape, jnp.int32)  # noqa: E731
    pool = sd(cfg.kv_pool_shape(65536), cfg.dtype)
    state_pool = sd(cfg.state_shape(256), cfg.dtype) if cfg.has_conv else None

    def stateful(**kw):  # the state arguments, for a config that has a state
        return {"state_pool": state_pool, **kw} if cfg.has_conv else {}

    if program == "decode_step":  # both row buckets the Qwen2 cells launch
        lowered = [decode_step.lower(
            params, cfg, ints(rows), pool, ints(rows), ints(rows, MAXP),
            ints(rows), PAGE, **stateful(state_slots=ints(2, rows)))
            for rows in (16, 32)]
    elif program == "prefill_chunk_paged":
        r = wave_rows
        lowered = [prefill_chunk_paged.lower(
            params, cfg, ints(r, CHUNK), ints(r, CHUNK), pool, ints(r, CHUNK),
            ints(r, 128), ints(r), page_size=PAGE, logits_at=ints(r),
            **stateful(state_in=ints(r), snap_at=ints(r, 3),
                       state_out=ints(r, 3)))]
    else:
        kv = sd((len(cfg.attn_layers), 1, 0, *cfg.kv_row[1:]), cfg.dtype)
        lowered = [prefill_forward.lower(
            params, cfg, ints(1, 1024), ints(1, 1024), kv, kv, ints(1),
            **({"snap_at": ints(1, 3)} if cfg.has_conv else {}))]
    for low in lowered:
        assert not _standalone_weight_ops(low.compile(), cfg.head_dim, weights)


def test_a_product_by_head_still_slices_and_copies_its_weight(v5e):
    """The other side: the forms the projections had until PR 40 — the
    reshape to heads right behind the ``wq`` product, ``wo`` contracted
    per head — under a layer scan at Qwen2-7B's widths. XLA writes both
    slices out and copies ``wq``'s transposed. If this stops failing, the
    barrier in ``_qkv`` and the flat ``wo`` are no longer needed."""
    sd = _sd(v5e)
    H, heads = 3584, 28

    def layer(x, lp):
        B, S = x.shape[:2]
        q = _wmm(lp, "wq", "bsh,hd->bsd", x) + lp["bq"]
        q = jax.nn.silu(q.reshape(B, S, heads, D))  # any consumer by head
        wo = lp["wo"].astype(x.dtype).reshape(heads, D, H)
        y = jnp.einsum("bsqd,qdh->bsh", q, wo, precision=_PREC) * lp["wo_s"]
        return x + y.astype(x.dtype), None

    stack = {
        "wq": sd((L, H, H), jnp.int8), "wq_s": sd((L, H), jnp.float32),
        "bq": sd((L, H), jnp.bfloat16),
        "wo": sd((L, H, H), jnp.int8), "wo_s": sd((L, H), jnp.float32),
    }
    compiled = jax.jit(lambda x, stack: jax.lax.scan(layer, x, stack)[0]).lower(
        sd((32, 1, H), jnp.bfloat16), stack).compile()
    found = _standalone_weight_ops(compiled, D, [stack["wq"]])
    assert sum("copy" in op for op in found) == 1, found
    assert sum("dynamic-slice_fusion" in op for op in found) == 2, found


@pytest.mark.parametrize("rows", [16, 64])
def test_the_kda_kernels_lower_at_ling3s_widths_and_ask_for_no_vmem(v5e, rows):
    """``radixmesh_kda_chunk`` / ``_decode`` / ``_boundary`` at 32 heads of
    128 x 128 over the cell's slot array: Mosaic takes them, none asks for
    VMEM of its own (a kernel that did hung inside a 64-row program, PR
    43), and a launch of more than 16 rows is calls of 16 (one call over
    32 rows beside the latent chunk kernel never returned on the chip, PR
    48)."""
    from radixmesh_tpu.ops import kda

    sd = _sd(v5e)
    H, d, T = 32, 128, 64
    f32, i32 = jnp.float32, jnp.int32
    pool = sd((6, 321, H * d, d), f32)
    seq = sd((rows, T, H, d), f32)

    def chunk(q, k, v, g, beta, pool, rows_in, fresh, snap, keep):
        return kda.kda_chunk(q, k, v, g, beta, pool, 2, rows_in, fresh, snap, keep)

    text = jax.jit(chunk, donate_argnums=(5,)).lower(
        seq, seq, seq, seq, sd((rows, T, H), f32), pool, sd((rows,), i32),
        sd((rows,), i32), sd((rows, 3), i32), sd((rows, 3), i32)).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "radixmesh_kda_chunk" in ln]
    assert len(calls) == -(-rows // kda._ROWS_A_CALL)
    assert all('"scoped_memory_configs":[]' in c for c in calls)
    assert all(f"f32[{kda._ROWS_A_CALL},{T},{H},{d}]" in c for c in calls)

    row = sd((rows, H, d), f32)

    def step(q, k, v, g, beta, pool, slots, keep):
        o, pool = kda.kda_decode(q, k, v, g, beta, pool, 2, slots)
        return o, kda.copy_slots(pool, slots, keep)

    text = jax.jit(step, donate_argnums=(5,)).lower(
        row, row, row, row, sd((rows, H), f32), pool, sd((rows,), i32),
        sd((rows,), i32)).compile().as_text()
    for name in ("radixmesh_kda_decode", "radixmesh_kda_boundary"):
        call, = [ln for ln in text.splitlines()
                 if "custom-call(" in ln and name in ln]
        assert '"scoped_memory_configs":[]' in call


@pytest.mark.parametrize("rows", [16, 64])
def test_the_ssd_kernels_lower_at_nemotron3s_widths_and_ask_for_no_vmem(v5e, rows):
    """``radixmesh_ssd_chunk`` / ``_decode`` at 64 heads of 64 x 128 in 8
    groups over the cell's slot array: Mosaic takes them, neither asks for
    VMEM of its own, and a launch of more than 16 rows is calls of 16
    (``ops/kda.py``'s reason)."""
    from radixmesh_tpu.ops import ssd

    sd = _sd(v5e)
    H, P, G, N, T = 64, 64, 8, 128, 128
    f32, i32 = jnp.float32, jnp.int32
    pool = sd((10, 321, H * P // 128 * N, 128), f32)

    def chunk(x, g, bm, cm, pool, rows_in, fresh, snap, keep):
        return ssd.ssd_chunk(x, g, bm, cm, pool, 2, rows_in, fresh, snap, keep)

    text = jax.jit(chunk, donate_argnums=(4,)).lower(
        sd((rows, T, H, P), f32), sd((rows, T, H), f32), sd((rows, T, G, N), f32),
        sd((rows, T, G, N), f32), pool, sd((rows,), i32), sd((rows,), i32),
        sd((rows, 3), i32), sd((rows, 3), i32)).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "radixmesh_ssd_chunk" in ln]
    assert len(calls) == -(-rows // ssd._ROWS_A_CALL)
    assert all('"scoped_memory_configs":[]' in c for c in calls)

    def step(x, g, bm, cm, pool, slots):
        return ssd.ssd_decode(x, g, bm, cm, pool, 2, slots)

    text = jax.jit(step, donate_argnums=(4,)).lower(
        sd((rows, H, P), f32), sd((rows, H), f32), sd((rows, G, N), f32),
        sd((rows, G, N), f32), pool, sd((rows,), i32)).compile().as_text()
    call, = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "radixmesh_ssd_decode" in ln]
    assert '"scoped_memory_configs":[]' in call


def _jamba2_launch(sd, page: int, slots: int = 2 ** 21, rows: int = 64):
    """Jamba2-3B's arguments as the cell's server holds them (2 M rows of
    1 KiB, 321 state slots of 8.9 MiB), for a table of 131 072 positions a
    row at ``page`` tokens a page."""
    from radixmesh_tpu.engine.engine import pool_groups

    cfg = get_config("jamba2-3b")
    params = jax.tree.map(
        lambda x: sd(x.shape, x.dtype),
        jax.eval_shape(lambda k: _init_params(k, cfg, None, None),
                       jax.ShapeDtypeStruct((2,), jnp.uint32)))
    groups = pool_groups(cfg, slots, rows, page)
    state = (sd(groups["state_shape"], groups["state_dtype"]),
             sd(groups["conv_state_shape"], cfg.dtype))
    return cfg, params, sd(cfg.kv_pool_shape(slots), cfg.dtype), state


def test_64_rows_of_131072_positions_lower_at_jamba2s_page_of_128(v5e):
    """ROADMAP R11, way A: the paged kernels scalar-prefetch a launch's
    whole page table, 8 B a row and page with the run flags, into 1 MiB of
    SMEM. At this model's page of 128 tokens 131 072 positions a row are
    1 024 entries: ``decode_step[64 rows]`` and the widest prefill wave (64
    rows x 64 tokens, and 8 x 512) compile for one v5e with the pool (2
    GiB) and the state slots (2.8 GiB) flowing through in place."""
    sd = _sd(v5e)
    i32, gib = jnp.int32, 2.0 ** 30
    page, rows, width = 128, 64, 131072 // 128
    cfg, params, pool, state = _jamba2_launch(sd, page)
    assert cfg.max_seq_len == width * page
    compiled = decode_step.lower(
        params, cfg, sd((rows,), i32), pool, sd((rows,), i32),
        sd((rows, width), i32), sd((rows,), i32), page, state_pool=state,
        state_slots=sd((2, rows), i32)).compile()
    mem = compiled.memory_analysis()
    assert "radixmesh_decode_fused_mh" in compiled.as_text()
    assert mem.alias_size_in_bytes / gib == pytest.approx(4.786, abs=0.01)
    assert mem.temp_size_in_bytes / gib < 0.05
    for r, c in ((64, 64), (8, 512)):
        compiled = prefill_chunk_paged.lower(
            params, cfg, sd((r, c), i32), sd((r, c), i32), pool, sd((r, c), i32),
            sd((r, width), i32), sd((r,), i32), page_size=page,
            logits_at=sd((r,), i32), state_pool=state, state_in=sd((r,), i32),
            snap_at=sd((r, 3), i32), state_out=sd((r, 3), i32)).compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        assert "radixmesh_chunk_attn" in text and "radixmesh_ssm_scan" in text
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes) / gib
        assert live < 11.5, f"the {r} x {c} wave needs {live:.2f} GiB"


def test_at_the_page_of_16_the_same_launch_is_refused(v5e):
    """The old bound, recorded beside the new one: the same 64 rows x
    131 072 positions at the page of 16 are 8 192 entries a row, 2.1 MB of
    table and run flags, and the compiler refuses the decode step's kernel
    for its scalar memory (what every other configuration's 16 384-49 152
    served positions stay under)."""
    sd = _sd(v5e)
    i32 = jnp.int32
    page, rows, width = 16, 64, 131072 // 16
    cfg, params, pool, state = _jamba2_launch(sd, page)
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED.*smem"):
        decode_step.lower(
            params, cfg, sd((rows,), i32), pool, sd((rows,), i32),
            sd((rows, width), i32), sd((rows,), i32), page, state_pool=state,
            state_slots=sd((2, rows), i32)).compile()
