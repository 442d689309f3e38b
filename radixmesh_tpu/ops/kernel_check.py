"""Kernel-vs-oracle parity at a caller-chosen geometry.

The unit tests (``tests/test_ops.py``) pin the Pallas kernels against
their jnp oracles in interpret mode at D=32, G in {1, 2, 4}. That proves
the algorithm; it says nothing about what Mosaic compiles for a real
model's head geometry on silicon. This module is the same comparison as
a callable: ``chip_smoke.py`` runs it on the chip at the served geometry
(Qwen2-7B: Hq 28, Hkv 4 → G 7, D 128, page 16, chunk 512; the decode
kernel also at Mellum2's Hq 32 → G 8 with a 1 024-key window; the chunk
kernel also over a prefix of several blocks, ``check_chunk_prefix``) for
the kernels the default ``serve`` flags reach, ``scripts/kernelbench.py``
runs it for the variants only a flag selects, and the CPU rehearsal runs
it in interpret mode at tiny size.

Every case compares a kernel called DIRECTLY with the reference branch
of its dispatcher in ``ops/attention.py`` (``use_kernel=False``) on the
same seeded inputs: ragged lengths (1 token, mid-page, page-aligned,
full), half the rows on consecutive page runs (the coalesced
one-descriptor DMA plan) and half on permuted pages (the per-page plan).
A case that raises — a kernel Mosaic refuses — is recorded with the
compiler's message, not skipped.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from radixmesh_tpu.ops.attention import (
    paged_attention_pool,
    paged_chunk_attention,
    paged_decode_attention,
)
from radixmesh_tpu.ops.mla_attention import (
    decode_groups,
    latent_decode_attention,
    mla_decode_kernel,
)
from radixmesh_tpu.ops.paged_attention import (
    paged_attention_pool_kernel,
    paged_chunk_attention_kernel,
    paged_decode_fused_kernel,
)
from radixmesh_tpu.ops import kda
from radixmesh_tpu.ops.quant import quantize_kv

__all__ = [
    "TOLERANCE", "DEFAULT_CASES", "FLAG_CASES", "WINDOW_CASES", "LATENT_CASES",
    "PREFIX_CASES", "KDA_CASES", "KDA_TOLERANCE", "check_kernels",
    "check_latent_decode", "check_chunk_prefix", "check_kda",
]

# Absolute AND relative bound on |kernel - oracle| per output element.
# Both sides read the same bf16 (or int8 + f32 scale) K/V and accumulate
# in f32; they differ by (a) the final rounding of an O(1) output to
# bf16, one ulp = 2^-8 ≈ 4e-3 relative, and (b) what the MXU is given.
# The heads-batched decode and pool kernels and (PR 43) the chunk kernel
# hand it the pool's own bf16:
# q·k is then exact (bf16 × bf16 products, f32 sums; 1/sqrt(D) applied to
# the f32 scores) and only ``p`` is rounded to bf16 for p·v, 2^-9 relative
# per weight. The per-head and int8 paths hand it f32 operands, over
# which it makes ONE bf16 pass (measured on the chip, PERF.md §6 PR 32:
# an f32 ``q`` against a bf16 pool reads 9e-5 mean error where the bf16
# rounding of the exact answer reads 6e-5): ``q·scale`` and ``p`` both
# rounded, ~2^-9 per score, i.e. ~1e-2 on a softmax weight. 2e-2 covers
# all of it with a margin of about two (the largest error the chip has
# shown is 7e-3); K/V held in anything narrower than the pool dtype
# (fp8's 2^-4 steps) lands at 6e-2 and fails.
TOLERANCE = 2e-2

# The linear-attention state kernels (``check_kda``, ``ops/kda.py``): a
# chunk from the rows' slots with snapshots inside it, one position a row
# through the slots in place, the boundary copies. Float32 against the same
# recurrence in ``jax.numpy``. The decode kernel differs from it by the
# order of two 128-term sums — 2.4e-7 on the states and 4e-8 on the outputs
# on the chip at 32 heads of 128 (PERF.md, PR 48); the chunk kernel is the
# blocked form, whose decays are ``exp`` of sums of up to 32 log-decays
# where the recurrence multiplies 32 factors — 2.5e-7 to 4.6e-7 on the
# outputs and 1.8e-6 to 1.8e-5 on the states it leaves, at chunks of 8 to
# 512 (PERF.md, PR 50) — and a state held in bf16 reads 4e-3 after ONE
# step. KDA_TOLERANCE lies between the two: this check is where the
# state's precision can be told on the chip (the served logits cannot: bf16
# activations cost three times what a bf16 state does,
# ``benchmarks/tools/ref_compare_kda.py``).
KDA_CASES = ("kda_chunk", "kda_decode", "kda_boundary")
KDA_TOLERANCE = 1e-4

# The Mamba-2 state kernels (``check_ssd``, ``ops/ssd.py``): a chunk from the
# rows' slots with snapshots inside it, one position a row through the slots
# in place (the boundary copies are ``check_kda``'s kernel). Float32 against
# the same recurrence in ``jax.numpy``: the chunk kernel is the chunked form
# (``exp`` of differences of a cumulative sum where the recurrence multiplies
# factors), a few 1e-6 at chunks of 16 to 512; a state held in bf16 reads
# 4e-3 after ONE step. SSD_TOLERANCE lies between the two, relative to the
# largest state the check holds (they grow with the chunk where the decays
# are near 1).
SSD_CASES = ("ssd_chunk", "ssd_decode")
SSD_TOLERANCE = 1e-4

# Kernels the default ``serve`` flags reach (bf16 pool, auto grid).
DEFAULT_CASES = ("fused_decode", "chunk_prefill")
# The decode kernel as a model with window layers compiles it (``window``
# a traced scalar; ``check_kernels(..., window=N)``): run with the window
# and with 0, against rows shorter than, at and past the window.
WINDOW_CASES = ("fused_decode_window",)
# The decode kernel of a latent (MLA) cache row (``check_latent_decode``):
# every row over its whole context, and the same launch with the rows that
# hold the same pages first told so (one shared pass, then each row's own).
LATENT_CASES = ("mla_decode", "mla_decode_grouped")
# The chunk kernel over a prefix of several blocks (``check_chunk_prefix``):
# what a turn of a session launches. Its rows' edge blocks, the blocks
# between them and the query blocks that hold no token.
PREFIX_CASES = ("chunk_prefill_prefix",)
# Variants only a flag selects: int8 KV (``--kv-quant int8``), the
# per-head grids (``fuse_heads=False``, huge-Hkv configs), and the
# read-only pool kernel (pp serving, the small-batch crossover).
FLAG_CASES = (
    "fused_decode_int8",
    "chunk_prefill_int8",
    "fused_decode_per_head",
    "fused_decode_int8_per_head",
    "pool_read",
    "pool_read_int8",
    "pool_read_per_head",
    "pool_read_int8_per_head",
)


def _inputs(hq, hkv, head_dim, page, batch, chunk, max_pages, seed):
    """Seeded inputs shared by every case: a 2-layer bf16 pool and its
    int8 twin, a page table (even rows consecutive runs, odd rows
    permuted), ragged lengths, decode and chunk queries."""
    rng = np.random.default_rng(seed)
    L, P = 2, batch * max_pages + 2
    kv = rng.standard_normal((2, L, hkv, P * page, head_dim)).astype(np.float32)
    kv8, scales = quantize_kv(jnp.asarray(kv), axis=-1)
    # Even rows own a consecutive page run; odd rows share out the rest,
    # shuffled. Rows never overlap: each decode row writes its own slot.
    pages = np.empty((batch, max_pages), np.int64)
    pages[0::2] = (
        np.arange(0, batch, 2)[:, None] * max_pages + np.arange(max_pages)
    )
    rest = np.setdiff1d(np.arange(batch * max_pages), pages[0::2].ravel())
    pages[1::2] = rng.permutation(rest).reshape(-1, max_pages)
    full = max_pages * page
    ragged = [1, page + 3, 5 * page, full, full - 1, 2 * page, 3, full // 2 + 1]
    lengths = np.asarray(
        [min(ragged[b % len(ragged)], full) for b in range(batch)], np.int32
    )
    pos = lengths - 1
    slots = pages[np.arange(batch), pos // page] * page + pos % page
    # Chunk prefill: prior context per row (none, mid-page, long), the
    # last row's chunk only partly valid.
    prior_cycle = [0, page // 2 + 1, full - chunk, 2 * page]
    prior = np.asarray(
        [max(0, min(prior_cycle[b % 4], full - chunk)) for b in range(batch)],
        np.int32,
    )
    valid = np.full((batch,), chunk, np.int32)
    valid[-1] = max(1, chunk // 3)

    def bf16(shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    return {
        "kv": jnp.asarray(kv, jnp.bfloat16).reshape(
            2, L, hkv, P, page, head_dim
        ),
        "kv8": kv8.reshape(2, L, hkv, P, page, head_dim),
        "scales": scales.reshape(2, L, hkv, P, page),
        "pt": jnp.asarray(pages, jnp.int32),
        "lengths": jnp.asarray(lengths),
        "slots": jnp.asarray(slots, jnp.int32),
        "q": bf16((batch, hq, head_dim)),
        "k_new": bf16((batch, hkv, head_dim)),
        "v_new": bf16((batch, hkv, head_dim)),
        "qc": bf16((batch, chunk, hq, head_dim)),
        "kc": bf16((batch, chunk, hkv, head_dim)),
        "vc": bf16((batch, chunk, hkv, head_dim)),
        "prior": jnp.asarray(prior),
        "kvlen": jnp.asarray(prior + valid),
        "chunk_valid": np.arange(chunk)[None, :] < valid[:, None],
    }


def _err(got, want, mask=None) -> float:
    """Largest ``|got - want| / (1 + |want|)`` over the compared elements
    (one number against one bound covers the absolute and the relative
    tolerance); inf when either side holds a non-finite value."""
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    if mask is not None:
        g, w = g[mask], w[mask]
    if not (np.isfinite(g).all() and np.isfinite(w).all()):
        return float("inf")
    return float(np.max(np.abs(g - w) / (1.0 + np.abs(w))))


def _fused_decode_case(x, kv, sc, layer, interpret, fuse, window) -> dict:
    got = paged_decode_fused_kernel(
        x["q"], x["k_new"], x["v_new"], kv, x["slots"], x["pt"],
        x["lengths"], layer, interpret=interpret, kv_scales=sc,
        fuse_heads=fuse, window=window,
    )
    want = paged_decode_attention(
        x["q"], x["k_new"], x["v_new"], kv, x["slots"], x["pt"],
        x["lengths"], layer, use_kernel=False, kv_scales=sc, window=window,
    )
    # The fused kernel also WRITES this token's K/V row: the pool it
    # returns must equal the reference's scatter everywhere, not just
    # where attention read it — exactly for a bf16 pool; within one
    # int8 step for a quantized one (the kernel wrapper quantizes the
    # f32 row, the reference the bf16 row).
    pool_diff = float(np.max(np.abs(
        np.asarray(got[1], np.float32) - np.asarray(want[1], np.float32)
    )))
    res = {
        "max_err": _err(got[0], want[0]),
        "pool_write_diff": pool_diff,
        "pool_write_ok": pool_diff <= (1.0 if sc is not None else 0.0),
    }
    if sc is not None:
        res["max_err"] = max(res["max_err"], _err(got[2], want[2]))
    return res


def _run_case(name: str, x: dict, interpret: bool) -> dict:
    int8 = "int8" in name
    fuse = False if name.endswith("per_head") else None
    kv = x["kv8"] if int8 else x["kv"]
    sc = x["scales"] if int8 else None
    layer = 1
    if name == "fused_decode_window":
        # One executable, as the scanned layer body has it: the window is
        # a traced scalar, 0 on the layers that attend everything.
        res = [
            _fused_decode_case(x, kv, sc, layer, interpret, fuse, jnp.int32(w))
            for w in (x["window"], 0)
        ]
        return max(res, key=lambda r: (not r["pool_write_ok"], r["max_err"]))
    if name.startswith("fused_decode"):
        return _fused_decode_case(x, kv, sc, layer, interpret, fuse, None)
    if name.startswith("chunk_prefill"):
        pos = x["prior"][:, None] + jnp.arange(x["qc"].shape[1])[None]
        got = paged_chunk_attention_kernel(
            x["qc"], x["kc"], x["vc"], kv, x["pt"], x["prior"], x["kvlen"],
            layer, interpret=interpret, kv_scales=sc,
        )
        want = paged_chunk_attention(
            x["qc"], x["kc"], x["vc"], kv, x["pt"], pos, x["prior"],
            x["kvlen"], layer, use_kernel=False, kv_scales=sc,
            # The oracle scans KV in blocks of this many pages, which
            # must divide the page-table width.
            kv_block_pages=int(np.gcd(x["pt"].shape[1], 32)),
        )
        return {"max_err": _err(got, want, x["chunk_valid"])}
    if name.startswith("pool_read"):
        got = paged_attention_pool_kernel(
            x["q"], kv, x["pt"], x["lengths"], layer, interpret=interpret,
            kv_scales=sc, fuse_heads=fuse,
        )
        want = paged_attention_pool(
            x["q"], kv, x["pt"], x["lengths"], layer, use_kernel=False,
            kv_scales=sc,
        )
        return {"max_err": _err(got, want)}
    raise ValueError(f"unknown kernel case {name!r}")


def _verdict(name: str, run, tolerance: float = TOLERANCE) -> dict:
    """One case's result dict: ``run()``'s numbers against the tolerance,
    or the exception it raised (the compiler's message when Mosaic refuses
    the kernel) — recorded, not skipped."""
    try:
        res = run()
    except Exception as exc:  # noqa: BLE001 — the verdict records the compiler's message; callers fail on ok=False
        return {
            "kernel": name, "ok": False,
            "error": f"{type(exc).__name__}: {exc}"[:2000],
        }
    return {
        "kernel": name,
        "ok": bool(
            res["max_err"] <= tolerance and res.get("pool_write_ok", True)
        ),
        "tolerance": tolerance,
        **res,
    }


def check_latent_decode(
    *,
    hq: int,
    width: int,
    v_width: int,
    page: int,
    batch: int = 8,
    max_pages: int = 128,
    block_tokens: int = 512,
    interpret: bool = False,
    seed: int = 0,
) -> list[dict]:
    """``LATENT_CASES`` at the given geometry, ``check_kernels``' result
    dicts: ``radixmesh_mla_decode`` against the gather reference on a bf16
    pool of one ``width``-wide row a token. All rows but the last two hold
    the same pages over the first five eighths of the table — consecutive
    ones (the one-descriptor copy) or permuted ones by ``seed``'s parity —
    two and a half blocks at the defaults, so two are shared; ragged
    lengths past them, one row of a single token."""
    rng = np.random.default_rng(seed)
    shared = max_pages * 5 // 8
    L, P = 2, 1 + shared + batch * max_pages
    first = 1 + (rng.permutation(shared) if seed % 2 else np.arange(shared))
    pages = 1 + shared + rng.permutation(batch * max_pages).reshape(
        batch, max_pages)
    pages[:-2, :shared] = first
    full = max_pages * page
    ragged = [full, shared * page + 1, full - page - 1, shared * page + page]
    lengths = np.asarray([ragged[b % 4] for b in range(batch)], np.int32)
    lengths[-1] = 1
    pos = lengths - 1
    slots = (pages[np.arange(batch), pos // page] * page + pos % page).astype(
        np.int32)
    pages = pages.astype(np.int32)
    pool = jnp.asarray(
        rng.standard_normal((1, L, 1, P, page, width)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((batch, hq, width)), jnp.bfloat16)
    new = jnp.asarray(rng.standard_normal((batch, width)), jnp.bfloat16)
    scale = float(width) ** -0.5
    want = latent_decode_attention(
        q, new, pool, slots, pages, lengths, 1, v_width, scale, use_kernel=False)
    groups, stats = decode_groups(
        pages, lengths, range(batch), page, block_tokens=block_tokens)

    def case(groups):
        got = mla_decode_kernel(
            q, new, pool, slots, pages, lengths, 1, groups, v_width=v_width,
            scale=scale, block_tokens=block_tokens, interpret=interpret)
        diff = float(np.max(np.abs(
            np.asarray(got[1], np.float32) - np.asarray(want[1], np.float32))))
        return {"max_err": _err(got[0], want[0], lengths > 1),
                "pool_write_diff": diff, "pool_write_ok": diff == 0.0}

    return [
        _verdict("mla_decode", lambda: case(None)),
        _verdict("mla_decode_grouped",
                 lambda: {**case(jnp.asarray(groups)), "groups": stats}),
    ]


def check_chunk_prefix(
    *,
    hq: int,
    hkv: int,
    head_dim: int,
    page: int,
    chunk: int,
    max_pages: int,
    window: int = 0,
    scale: float | None = None,
    interpret: bool = False,
    seed: int = 0,
) -> list[dict]:
    """``PREFIX_CASES`` at the given geometry, ``check_kernels``' result
    dicts: ``radixmesh_chunk_attn`` against the gather reference on a bf16
    pool, four rows of one launch. Row 0 holds one ascending run of pages
    and a prefix that ends mid-page, as deep as the table allows; row 1
    permuted pages, a prefix of whole pages and a chunk whose last three
    quarters hold no token; row 2 nothing at all (a row on the scratch
    slot: its output must be zeros); row 3 permuted pages and a prefix of
    one page and three tokens. The kernel's pool holds NaN in every slot of
    a row's last prefix page past the prefix — fetched, never visible.
    With ``window`` the call runs twice through one traced scalar, with it
    and with 0 (a model's window and full layers share an executable)."""
    rng = np.random.default_rng(seed)
    B, L, full = 4, 2, max_pages * page
    P = B * max_pages
    pages = np.concatenate([  # one run, then the other rows' own, shuffled
        np.arange(max_pages),
        max_pages + rng.permutation((B - 1) * max_pages),
    ]).reshape(B, max_pages).astype(np.int32)
    deep = full - chunk
    prior = np.asarray(
        [deep - page // 2, deep // page // 2 * page, 0, page + 3], np.int32)
    new = np.asarray([chunk, max(1, chunk // 4 - 3), 0, chunk], np.int32)
    kv = rng.standard_normal((2, L, hkv, P, page, head_dim)).astype(np.float32)
    dirty = kv.copy()
    for b in range(B):
        if prior[b] % page:
            dirty[:, :, :, pages[b, prior[b] // page], prior[b] % page:] = np.nan

    def bf16(shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    q = bf16((B, chunk, hq, head_dim))
    kc, vc = bf16((B, chunk, hkv, head_dim)), bf16((B, chunk, hkv, head_dim))
    pt, pr, kvlen = jnp.asarray(pages), jnp.asarray(prior), jnp.asarray(prior + new)
    pos = pr[:, None] + jnp.arange(chunk)[None]
    valid = np.arange(chunk)[None, :] < new[:, None]

    def case(w):
        w = None if w is None else jnp.int32(w)
        got = paged_chunk_attention_kernel(
            q, kc, vc, jnp.asarray(dirty, jnp.bfloat16), pt, pr, kvlen, 1,
            interpret=interpret, window=w, scale=scale,
        )
        want = paged_chunk_attention(
            q, kc, vc, jnp.asarray(kv, jnp.bfloat16), pt, pos, pr, kvlen, 1,
            use_kernel=False, window=w, scale=scale,
            kv_block_pages=int(np.gcd(max_pages, 32)),
        )
        idle = float(np.max(np.abs(np.asarray(got[2], np.float32))))
        return {"max_err": max(_err(got, want, valid), idle),
                "idle_row_max": idle}

    def run():
        if not window:
            return case(None)
        return max((case(window), case(0)), key=lambda r: r["max_err"])

    return [_verdict(PREFIX_CASES[0], run)]


def check_kernels(
    cases: tuple[str, ...],
    *,
    hq: int,
    hkv: int,
    head_dim: int,
    page: int,
    batch: int = 8,
    chunk: int = 512,
    max_pages: int = 64,
    interpret: bool = False,
    seed: int = 0,
    window: int = 0,
) -> list[dict]:
    """Run each named case at the given geometry; one result dict per
    case: ``{"kernel", "ok", "max_err", "tolerance"}`` with ``max_err``
    the largest ``|kernel - oracle| / (1 + |oracle|)`` (the fused cases
    add the pool-write comparison), or ``{"kernel", "ok": False,
    "error"}`` carrying the exception (the compiler's message when Mosaic
    refuses the kernel)."""
    if chunk > max_pages * page:
        raise ValueError(
            f"chunk {chunk} exceeds the table's {max_pages * page} tokens"
        )
    if "fused_decode_window" in cases and not 0 < window < max_pages * page:
        raise ValueError(
            f"fused_decode_window needs 0 < window ({window}) < the table's "
            f"{max_pages * page} tokens: a context past the window"
        )
    x = _inputs(hq, hkv, head_dim, page, batch, chunk, max_pages, seed)
    x["window"] = window
    return [_verdict(name, lambda: _run_case(name, x, interpret))
            for name in cases]


def check_kda(
    *,
    heads: int,
    dim: int,
    batch: int = 4,
    chunk: int = 64,
    slots: int = 20,
    interpret: bool = False,
    seed: int = 0,
    state_dtype=None,
) -> list[dict]:
    """``KDA_CASES`` at the given geometry, ``check_kernels``' result
    dicts: each kernel of ``ops/kda.py`` called DIRECTLY against its
    ``jax.numpy`` form on the same seeded inputs — gates drawn over their
    whole range (the bound of -5 included), one row fresh and the others
    resuming from their slots, snapshots at a chunk's end, inside it and at
    0; a decode launch over distinct slots; copies for half the rows. Held
    to ``KDA_TOLERANCE``. ``state_dtype`` is the control: the states the
    kernels wrote, as slots of that dtype would hold them (bf16 has to
    fail)."""
    rng = np.random.default_rng(seed)
    B, T, H, d = batch, chunk, heads, dim

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def unit(t):
        return t / jnp.linalg.norm(t, axis=-1, keepdims=True)

    q, k = unit(normal(B, T, H, d)) * d ** -0.5, unit(normal(B, T, H, d))
    v = normal(B, T, H, d)
    g = -5.0 / (1.0 + jnp.exp(-4.0 * normal(B, T, H, d)))
    g = g.at[0, : T // 2].set(-5.0)  # a run at the bound
    beta = 1.0 / (1.0 + jnp.exp(-normal(B, T, H)))
    pool = normal(2, slots, H * d, d)
    rows = jnp.asarray(rng.permutation(np.arange(1, slots))[:B], jnp.int32)
    fresh = jnp.zeros((B,), jnp.int32).at[-1].set(1)
    snap = jnp.asarray([[T, max(T // 2 - 3, 0), 0]] * B, jnp.int32)
    layer = jnp.asarray(1, jnp.int32)
    taken = set(np.asarray(rows).tolist())
    free = [s_ for s_ in range(1, slots) if s_ not in taken]  # nobody's start

    def written(pool_out, want, held=slice(None)) -> dict:
        got = jnp.asarray(pool_out)
        if state_dtype is not None:
            got = got.astype(state_dtype).astype(jnp.float32)
        diff = float(np.max(np.abs(
            np.asarray(got)[:, held] - np.asarray(want)[:, held])))
        return {"pool_write_diff": diff, "pool_write_ok": diff <= KDA_TOLERANCE}

    def chunk_case():
        # every state to a slot of its own, but row 0's last (0: not kept)
        keep = jnp.asarray(free[:3 * B], jnp.int32).reshape(B, 3).at[0, 2].set(0)
        want = kda.kda_chunk(q, k, v, g, beta, pool, layer, rows, fresh, snap,
                             keep, use_kernel=False)
        got = kda.kda_chunk_kernel(q, k, v, g, beta, pool + 0, layer, rows,
                                   fresh, snap, keep, interpret=interpret)
        held = np.ones((slots,), bool)
        held[0] = False  # the reference leaves what nobody keeps there
        return {"max_err": _err(got[0], want[0]), **written(got[1], want[1], held)}

    step = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])

    def decode_case():
        want = kda.kda_decode_ref(*step, pool, layer, rows)
        got = kda.kda_decode_kernel(*step, pool + 0, layer, rows,
                                    interpret=interpret)
        return {"max_err": _err(got[0], want[0]), **written(got[1], want[1])}

    def boundary_case():
        dst = jnp.where(jnp.arange(B) % 2 == 0,
                        jnp.asarray(free[:B], jnp.int32), 0)
        got = kda.copy_slots_kernel(pool + 0, rows, dst, interpret=interpret)
        want = np.asarray(pool).copy()
        for s_, d_ in zip(np.asarray(rows), np.asarray(dst)):
            if d_:
                want[:, d_] = want[:, s_]
        diff = float(np.max(np.abs(np.asarray(got) - want)))
        return {"max_err": diff, "pool_write_diff": diff,
                "pool_write_ok": diff == 0.0}

    return [_verdict(name, case, KDA_TOLERANCE) for name, case in zip(
        KDA_CASES, (chunk_case, decode_case, boundary_case))]


def check_ssd(
    *,
    heads: int,
    dim: int,
    groups: int,
    state: int,
    batch: int = 4,
    chunk: int = 128,
    slots: int = 20,
    interpret: bool = False,
    seed: int = 0,
    state_dtype=None,
) -> list[dict]:
    """``SSD_CASES`` at the given geometry, ``check_kernels``' result dicts:
    each kernel of ``ops/ssd.py`` called DIRECTLY against its ``jax.numpy``
    form on the same seeded inputs — decays drawn from e^-4.5 to 0.9975 a
    token with a run at ``Delta A`` = -40, one row fresh and the others
    resuming from their slots, snapshots at a chunk's end, inside it and at
    0; a decode launch over distinct slots. Differences relative to the
    largest state, held to ``SSD_TOLERANCE``. ``state_dtype`` is the
    control: the states the kernels wrote, as slots of that dtype would hold
    them (bf16 has to fail)."""
    from radixmesh_tpu.ops import ssd

    rng = np.random.default_rng(seed)
    B, T, H, P, G, N = batch, chunk, heads, dim, groups, state

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    x, bm, cm = normal(B, T, H, P), normal(B, T, G, N), normal(B, T, G, N)
    g = -jnp.exp(jnp.asarray(rng.uniform(-6.0, 1.5, (B, T, H)), jnp.float32))
    g = g.at[0, : T // 2].set(-40.0)
    pool = normal(2, slots, H * P // 128 * N, 128)
    rows = jnp.asarray(rng.permutation(np.arange(1, slots))[:B], jnp.int32)
    fresh = jnp.zeros((B,), jnp.int32).at[-1].set(1)
    snap = jnp.asarray([[T, max(T // 2 - 3, 0), 0]] * B, jnp.int32)
    layer = jnp.asarray(1, jnp.int32)
    taken = set(np.asarray(rows).tolist())
    free = [s_ for s_ in range(1, slots) if s_ not in taken]  # nobody's start

    def written(pool_out, want, held=slice(None)) -> dict:
        got = jnp.asarray(pool_out)
        if state_dtype is not None:
            got = got.astype(state_dtype).astype(jnp.float32)
        want = np.asarray(want)[:, held]
        diff = float(np.max(np.abs(np.asarray(got)[:, held] - want))
                     / max(1.0, np.max(np.abs(want))))
        return {"pool_write_diff": diff, "pool_write_ok": diff <= SSD_TOLERANCE}

    def far(got, want) -> float:
        """``max|got - want|`` against the LARGEST output: a decay is ``exp``
        of a difference of cumulative sums, exact to the sums' float32 step,
        which an output near zero beside one of 80 cannot be held to."""
        want = np.asarray(want)
        return float(np.max(np.abs(np.asarray(got) - want))
                     / max(1.0, np.max(np.abs(want))))

    def chunk_case():
        keep = jnp.asarray(free[:3 * B], jnp.int32).reshape(B, 3).at[0, 2].set(0)
        want = ssd.ssd_chunk(x, g, bm, cm, pool, layer, rows, fresh, snap, keep,
                             use_kernel=False)
        got = ssd.ssd_chunk_kernel(x, g, bm, cm, pool + 0, layer, rows, fresh,
                                   snap, keep, interpret=interpret)
        held = np.ones((slots,), bool)
        held[0] = False  # the reference leaves what nobody keeps there
        return {"max_err": far(got[0], want[0]), **written(got[1], want[1], held)}

    def decode_case():
        step = (x[:, 0], g[:, 0], bm[:, 0], cm[:, 0])
        want = ssd.ssd_decode_ref(*step, pool, layer, rows)
        got = ssd.ssd_decode_kernel(*step, pool + 0, layer, rows,
                                    interpret=interpret)
        return {"max_err": far(got[0], want[0]), **written(got[1], want[1])}

    return [_verdict(name, case, SSD_TOLERANCE) for name, case in zip(
        SSD_CASES, (chunk_case, decode_case))]
