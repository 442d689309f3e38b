"""Learned sparse attention (DSA) over the paged latent cache: a small
indexer scores every cached token, the query attends the best ``topk``.

A layer with an indexer ("full") caches one more thing a token: its index
key ``k^I`` (``index_head_dim`` wide, bf16), in a pool of its own
``[L_full, slots, Di]`` addressed by the SAME slot numbers as the latent
rows (``cache/kv_pool.py``). For query ``t`` with index queries ``q^I_j``
(``Hi`` heads) and head weights ``w_j`` (already scaled):

    I_s = sum_j w_j * relu(q^I_j . k^I_s)          for s <= t
    S_t = the topk largest I_s                     (all s <= t while t < topk)
    o_i = sum_{s in S_t} softmax_S(scale * q_i . row_s) row_s[:v_width]

Scores and selection are float32. A "shared" layer has no indexer and
attends the set of the nearest full layer before it; the set rides the
layer scan as ``Selection``.

The unit of work is a GROUP: ``Cq`` queries of one sequence, over that
sequence's ``prior`` tokens in pages and over ``Co`` own tokens that are
not in pages yet (the decode step's one new token, a prefill chunk's
tokens). A decode step is ``B`` groups of one query; a prefill chunk is
cut into groups of at most ``_TILE`` queries, run one after another
(``lax.map``) so that the gathered rows of a tile, ``_TILE x topk`` rows,
bound the temporary. Four pieces, each named for the device trace:

- ``radixmesh_dsa_index_decode`` / ``radixmesh_dsa_index_chunk`` — Pallas:
  a group's index keys read where they lie in pages, block by block
  through a double buffer; ``[Cq*Hi, Di] x [Di, block]`` on the MXU, relu,
  the weighted sum over heads as a second product. They read the index
  pool and never write it (a pool that a ``lax.cond`` branch returns is
  copied whole, twice a layer: 4 ms of a decode step); the caller scatters
  the new keys.
- ``radixmesh_dsa_select`` (a ``jax.named_scope``) — XLA: the exact
  top-``topk`` over the prior scores and the own scores together, each
  score tagged with what a pick has to say (page and position, or the own
  token's number): ``lax.top_k`` for a short row, for a long one a k-th
  value by bisection and a compaction by segment (``_pick_by_threshold``:
  a full sort of 65 k scores a row was 15% of a decode step).
- the gather of the selected rows — XLA (``radixmesh_dsa_gather``
  scope): one row of the pool a selected token, nothing else is read.
- ``radixmesh_mla_sparse_decode`` / ``radixmesh_mla_sparse_chunk`` —
  Pallas: one query a program over its gathered rows and the group's own
  rows (a single softmax over both); the decode variant writes the new
  latent row through the aliased pool.

Any other lowering target (the CPU) takes ``jax.numpy`` references of the
two kernels; selection and gather are the same code everywhere.
"""

# meshcheck: file-ok[timeout-audit] every wait() in this file is a
# pallas device-semaphore / copy-descriptor wait — a kernel DSL op
# completing an async device DMA, not a thread parking on a peer.

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from radixmesh_tpu.ops.mla_attention import _RowBlockCopy, _by_target
from radixmesh_tpu.ops.paged_attention import _block_geometry, _contig_flags

__all__ = [
    "Selection",
    "empty_selection",
    "index_scores",
    "select",
    "dense_selection_mask",
    "sparse_attention",
    "decode_select",
    "decode_attend",
    "chunk_select",
    "chunk_attend",
    "masked_prefill_attention",
]

NEG = -3.0e38  # a masked score / bias (finite: no inf - inf)
_PREC = jax.lax.Precision.HIGHEST
_VMEM_LIMIT = 64 * 1024 * 1024
_INDEX_BLOCK_TOKENS = 2048  # a block's DMA: 2048 keys of 256 B = 512 KB (0.5 us a block at 512)
_TILE = 32  # queries of a prefill chunk handled at once


class Selection(NamedTuple):
    """What a full layer selected, for itself and the shared layers after
    it. ``slots`` [N, Cq, K] pool slots of the selected prior tokens
    (any valid slot where ``bias`` masks); ``bias`` [N, Cq, K] float32, 0
    on a selected entry, ``NEG`` elsewhere; ``own_bias`` [N, Cq, Co] the
    same for the group's own tokens; ``positions`` [N, Cq, K] the token
    positions selected, prior and own alike (-1: nothing), for a reader
    (the logits hook): no kernel takes them."""

    slots: jnp.ndarray
    bias: jnp.ndarray
    own_bias: jnp.ndarray
    positions: jnp.ndarray


def _scored_tokens(max_pages: int, page: int) -> int:
    """Width of a group's prior scores: the page table padded to whole
    blocks of the index kernel, in tokens."""
    ppb = min(max(1, _INDEX_BLOCK_TOKENS // page), max_pages)
    return -(-max_pages // ppb) * ppb * page


def empty_selection(N: int, Cq: int, Co: int, max_pages: int, page: int,
                    topk: int) -> Selection:
    """The ``Selection`` a layer scan starts from (its first layer is a
    full one and replaces it): the shapes ``select`` gives."""
    k = min(topk, _scored_tokens(max_pages, page) + Co)
    return Selection(
        jnp.zeros((N, Cq, k), jnp.int32), jnp.full((N, Cq, k), NEG, jnp.float32),
        jnp.full((N, Cq, Co), NEG, jnp.float32),
        jnp.full((N, Cq, k), -1, jnp.int32),
    )


# ---------------------------------------------------------------------------
# index scoring
# ---------------------------------------------------------------------------


def _index_kernel(prior_ref, page_table_ref, contig_ref, layer_ref, q_ref, w_ref,
                  pool_hbm, o_ref, buf, sems, *, page: int, pages_per_block: int,
                  pages_per_seq: int):
    """One group: scores of its ``Cq`` queries against the index keys in
    its pages, block ``i`` into ``o_ref[i]``. Blocks past ``prior`` are
    not written (the wrapper masks them)."""
    n = pl.program_id(0)
    prior = prior_ref[n]
    bk = page * pages_per_block
    nblocks = pages_per_seq // pages_per_block
    src = pool_hbm.at[layer_ref[0]]  # [P, page, Di]

    def copy(i, s):
        return _RowBlockCopy(
            src, buf.at[s], sems.at[s], page_table_ref,
            n * pages_per_seq + i * pages_per_block, pages_per_block,
            contig_ref[n * nblocks + i],
        )

    n_blocks = pl.cdiv(prior, bk)

    @pl.when(n_blocks > 0)
    def _cold_start():
        copy(0, 0).start()

    q = q_ref[...]  # [Cq * Hi, Di]
    w = w_ref[...]  # [Cqp, Cq * Hi] float32, block-diagonal

    def body(i, _):
        s = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_blocks)
        def _prefetch_next():
            copy(i + 1, 1 - s).start()

        copy(i, s).wait()
        keys = buf[s].reshape(bk, -1)
        dots = jax.lax.dot_general(  # [Cq * Hi, bk]
            q, keys, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[i] = jax.lax.dot_general(  # [Cqp, bk]
            w, jnp.maximum(dots, 0.0),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )
        return ()

    jax.lax.fori_loop(0, n_blocks, body, ())


@functools.partial(
    jax.jit, static_argnames=("site", "block_tokens", "interpret"))
def index_scores_kernel(
    qi: jnp.ndarray,  # [N, Cq, Hi, Di] index queries, pool dtype
    wi: jnp.ndarray,  # [N, Cq, Hi] float32 head weights (scaled)
    index_pages: jnp.ndarray,  # [L_full, P, page, Di]
    page_table: jnp.ndarray,  # [N, max_pages]
    prior: jnp.ndarray,  # [N] tokens in pages that the group scores
    layer: jnp.ndarray | int,  # which full layer's keys
    *,
    site: str,
    block_tokens: int = _INDEX_BLOCK_TOKENS,
    interpret: bool = False,
):
    """``scores [N, Cq, S]`` float32 (``S`` = the block-padded table x
    page; entries at or past ``prior`` are undefined). The pool is read,
    never written: a token's key is scored from its caller's hands
    (``own_scores``) and stored by its caller."""
    N, Cq, Hi, Di = qi.shape
    P, page = index_pages.shape[1:3]
    dtype = index_pages.dtype
    page_table, ppb, padded = _block_geometry(
        page_table, page, max(1, block_tokens // page))
    nb, bk = padded // ppb, ppb * page
    prior = jnp.asarray(prior, jnp.int32)
    contig = _contig_flags(page_table, prior, page, ppb, P)
    cqp = -(-Cq // 8) * 8  # a whole float32 sublane tile of output rows
    m = Cq * Hi
    w_blk = (wi.astype(jnp.float32)[:, :, None, :]
             * jnp.eye(Cq, dtype=jnp.float32)[None, :, :, None]).reshape(N, Cq, m)
    w_blk = jnp.pad(w_blk, ((0, 0), (0, cqp - Cq), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_index_kernel, page=page, pages_per_block=ppb,
                          pages_per_seq=padded),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(N,),
            in_specs=[
                pl.BlockSpec((None, m, Di), lambda n, *_: (n, 0, 0)),
                pl.BlockSpec((None, cqp, m), lambda n, *_: (n, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, nb, cqp, bk), lambda n, *_: (n, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, ppb, page, Di), dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((N, nb, cqp, bk), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name=f"radixmesh_dsa_index_{site}",
    )(prior, jnp.asarray(page_table, jnp.int32).reshape(-1), contig,
      jnp.asarray(layer, jnp.int32).reshape(1),
      qi.astype(dtype).reshape(N, m, Di), w_blk, index_pages)
    # [N, nb, Cqp, bk] -> [N, Cq, nb * bk]
    return out[:, :, :Cq].transpose(0, 2, 1, 3).reshape(N, Cq, nb * bk)


def _index_scores_ref(qi, wi, index_pages, page_table, layer):
    """The same scores from gathered keys, ``[N, Cq, max_pages * page]``."""
    N = qi.shape[0]
    keys = index_pages[layer][page_table]  # [N, max_pages, page, Di]
    keys = keys.reshape(N, -1, keys.shape[-1]).astype(jnp.float32)
    dots = jnp.einsum("nchd,nsd->nchs", qi.astype(jnp.float32), keys,
                      precision=_PREC)
    return jnp.einsum("nchs,nch->ncs", jnp.maximum(dots, 0.0),
                      wi.astype(jnp.float32), precision=_PREC)


def index_scores(
    qi, wi, index_pages, page_table, prior, layer, *, site: str,
    use_kernel: bool | None = None, interpret: bool = False,
):
    """A group's index scores over its pages, masked with ``NEG`` at and
    past ``prior``: ``[N, Cq, S]`` float32."""
    qi = qi.astype(index_pages.dtype)

    def kernel(qi, wi, index_pages, page_table, prior, layer):
        return index_scores_kernel(
            qi, wi, index_pages, page_table, prior, layer, site=site,
            interpret=interpret,
        )

    def reference(qi, wi, index_pages, page_table, prior, layer):
        scores = _index_scores_ref(qi, wi, index_pages, page_table, layer)
        # The kernel's width: the table padded to whole blocks.
        pad = _scored_tokens(
            page_table.shape[1], index_pages.shape[2]) - scores.shape[-1]
        return jnp.pad(scores, ((0, 0), (0, 0), (0, pad)))

    scores = _by_target(
        use_kernel, kernel, reference, qi, wi, index_pages,
        jnp.asarray(page_table, jnp.int32), jnp.asarray(prior, jnp.int32),
        jnp.asarray(layer, jnp.int32),
    )
    seen = jnp.arange(scores.shape[-1])[None, None, :] < prior[:, None, None]
    return jnp.where(seen, scores, NEG)


def own_scores(qi, wi, own_keys, own_ok):
    """Index scores of a group's queries against its own tokens' keys
    (both in the cache's dtype, as a later read from pages will see
    them): ``[N, Cq, Co]``, ``NEG`` where ``own_ok`` [N, Cq, Co] is
    False."""
    dots = jnp.einsum(
        "nchd,nod->ncho", qi.astype(jnp.float32),
        own_keys.astype(jnp.float32), precision=_PREC,
    )
    s = jnp.einsum("ncho,nch->nco", jnp.maximum(dots, 0.0),
                   wi.astype(jnp.float32), precision=_PREC)
    return jnp.where(own_ok, s, NEG)


# ---------------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------------


_POS_BITS = 17  # a tag is page id << 17 | position: 128 k positions, 16 k pages
_THRESHOLD_FROM = 8192  # scores a row from which the selection avoids the sort


def _pick_by_sort(scores, tags, k: int):
    """The tags of the ``k`` largest ``scores`` [R, W] a row (ties to the
    lower index) and which of them are visible: ``lax.top_k``, a full sort
    of every row on the chip."""
    vals, idx = jax.lax.top_k(scores, k)
    return jnp.take_along_axis(tags, idx, axis=-1), vals > NEG


def _pick_by_threshold(scores, tags, k: int):
    """The same set without sorting a row (2.7 ms for 32 x 65 537 on a v5e,
    twice a decode step): the k-th largest value by bisection on the
    scores' bits (32 counting passes), the ties at it taken in index
    order, then a compaction — each 128-score segment sorted on
    selected / not (a stable 128-element sort: 0.3 ms for all of them),
    the output's j-th entry located by the segments' running counts and
    fetched with ONE gather. The picks come in index order, not score
    order: the selection is a set."""
    R, W = scores.shape
    pad = -W % 128
    scores = jnp.pad(scores, ((0, 0), (0, pad)), constant_values=NEG)
    tags = jnp.pad(tags, ((0, 0), (0, pad)))
    nseg = (W + pad) // 128
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    # float order as unsigned order; a masked score is the smallest key
    key = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    seen = scores > NEG
    key = jnp.where(seen, key, jnp.uint32(0))

    def bit(i, t):  # the largest t with at least k keys >= t
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(key >= cand[:, None], axis=-1) >= k
        return jnp.where(enough, cand, t)

    t = jax.lax.fori_loop(0, 32, bit, jnp.zeros((R,), jnp.uint32))[:, None]
    above = key > t
    tie = ((key == t) & seen).reshape(R, nseg, 128)
    need = k - jnp.sum(above, axis=-1)  # ties to take, in index order
    tie_seg = jnp.sum(tie, axis=-1)
    tie_rank = (jnp.cumsum(tie, axis=-1)  # inclusive rank of a tie in its row
                + (jnp.cumsum(tie_seg, axis=-1) - tie_seg)[..., None])
    sel = above.reshape(R, nseg, 128) | (tie & (tie_rank <= need[:, None, None]))
    count = jnp.sum(sel, axis=-1).astype(jnp.int32)  # [R, nseg]
    upto = jnp.cumsum(count, axis=-1)
    # selected scores first, each part in index order: the keys are unique,
    # so the sort need not be stable (a stable one carries a third operand)
    lane = jnp.arange(128, dtype=jnp.int32)
    _, packed = jax.lax.sort(
        (jnp.where(sel, lane, 128 + lane), tags.reshape(R, nseg, 128)),
        dimension=-1, num_keys=1, is_stable=False,
    )
    j = jnp.arange(k, dtype=jnp.int32)
    before = (upto[:, None, :] <= j[None, :, None])  # segments wholly before j
    seg = jnp.minimum(jnp.sum(before, axis=-1), nseg - 1)
    start = jnp.einsum("rkn,rn->rk", before.astype(jnp.float32),
                       count.astype(jnp.float32), precision=_PREC).astype(jnp.int32)
    at = seg * 128 + jnp.clip(j[None, :] - start, 0, 127)
    picked = jnp.take_along_axis(packed.reshape(R, nseg * 128), at, axis=-1)
    return picked, j[None, :] < upto[:, -1:]


def select(prior_scores, own, page_table, prior, page: int,
           topk: int) -> Selection:
    """Exact top-``topk`` over a group's prior scores [N, Cq, S] and own
    scores [N, Cq, Co] together (masked entries are ``NEG``; a tie goes to
    the lower position): the prior part as pool slots through
    ``page_table`` [N, max_pages], the own part (tokens at positions
    ``prior`` [N] on) as a mask. Fewer than ``topk`` visible entries
    select them all."""
    with jax.named_scope("radixmesh_dsa_select"):
        N, Cq, S = prior_scores.shape
        Co = own.shape[-1]
        k = min(topk, S + Co)
        # What a pick has to say, as one int32 a score: a prior token's page
        # and position (its slot follows), an own token's number as -1 - j.
        pos = jnp.arange(S, dtype=jnp.int32)
        pages = jnp.repeat(page_table, page, axis=1)  # [N, max_pages * page]
        pages = jnp.pad(pages, ((0, 0), (0, max(0, S - pages.shape[1]))))[:, :S]
        tags = jnp.concatenate([
            (pages << _POS_BITS) | pos[None, :],
            jnp.broadcast_to(-1 - jnp.arange(Co, dtype=jnp.int32), (N, Co)),
        ], axis=-1)
        tags = jnp.broadcast_to(tags[:, None, :], (N, Cq, S + Co)).reshape(N * Cq, -1)
        both = jnp.concatenate([prior_scores, own], axis=-1).reshape(N * Cq, -1)
        long = _THRESHOLD_FROM <= S + Co < (1 << _POS_BITS)
        pick = _pick_by_threshold if long else _pick_by_sort
        picked, seen = pick(both, tags, k)
        picked, seen = picked.reshape(N, Cq, k), seen.reshape(N, Cq, k)
        ok = seen & (picked >= 0)
        at = jnp.where(ok, picked & ((1 << _POS_BITS) - 1), 0)
        own_in = jnp.any(
            (picked[..., None] == -1 - jnp.arange(Co, dtype=jnp.int32))
            & seen[..., None], axis=-2) & (own > NEG)
        return Selection(
            slots=jnp.where(ok, (picked >> _POS_BITS) * page + at % page, 0
                            ).astype(jnp.int32),
            bias=jnp.where(ok, 0.0, NEG).astype(jnp.float32),
            own_bias=jnp.where(own_in, 0.0, NEG).astype(jnp.float32),
            positions=jnp.where(
                seen, jnp.where(picked >= 0, at, -1 - picked + prior[:, None, None]),
                -1).astype(jnp.int32),
        )


def dense_selection_mask(scores: jnp.ndarray, topk: int) -> jnp.ndarray:
    """The selection as a mask over dense scores ``[..., S]`` (``NEG``
    where a key is not visible): True on the ``topk`` largest visible
    (ties to the lower position, as ``select``)."""
    with jax.named_scope("radixmesh_dsa_select"):
        vals, idx = jax.lax.top_k(scores, min(topk, scores.shape[-1]))
        return jnp.put_along_axis(
            jnp.zeros(scores.shape, bool), idx, vals > NEG, axis=-1,
            inplace=False,
        )


# ---------------------------------------------------------------------------
# attention over the selected rows
# ---------------------------------------------------------------------------


def _sparse_kernel(*refs, v_width: int, page: int, write: bool):
    """One query: its heads over its gathered rows and the group's own
    rows, one softmax over both."""
    if write:
        (slots_ref, layer_ref, q_ref, g_ref, b_ref, own_ref, ob_ref,
         pool_hbm, pool_out, o_ref, row_scr, w_sem) = refs
        del pool_hbm
        n = pl.program_id(0)
        slot = slots_ref[n]
        dst = pool_out.at[0, layer_ref[0], 0, slot // page]  # (page, W)
        read_page = pltpu.make_async_copy(dst, row_scr, w_sem)
        write_page = pltpu.make_async_copy(row_scr, dst, w_sem)
        read_page.start()
        read_page.wait()
        mine = jax.lax.broadcasted_iota(
            jnp.int32, row_scr.shape, 0) == slot % page
        row_scr[...] = jnp.where(
            mine, jnp.broadcast_to(own_ref[:1], row_scr.shape), row_scr[...]
        )
        write_page.start()
    else:
        q_ref, g_ref, b_ref, own_ref, ob_ref, o_ref = refs

    q = q_ref[...]  # [R, W] pre-scaled
    g = g_ref[...]  # [K, W]
    own = own_ref[...]  # [Co, W]
    bias, own_bias = b_ref[...], ob_ref[...]  # [1, K], [1, Co]
    contract = (((1,), (1,)), ((), ()))
    s_g = jax.lax.dot_general(
        q, g, dimension_numbers=contract, preferred_element_type=jnp.float32
    ) + bias
    s_o = jax.lax.dot_general(
        q, own, dimension_numbers=contract, preferred_element_type=jnp.float32
    ) + own_bias
    m = jnp.maximum(jnp.max(s_g, axis=-1, keepdims=True),
                    jnp.max(s_o, axis=-1, keepdims=True))
    p_g = jnp.where(bias > 0.5 * NEG, jnp.exp(s_g - m), 0.0)
    p_o = jnp.where(own_bias > 0.5 * NEG, jnp.exp(s_o - m), 0.0)
    total = (jnp.sum(p_g, axis=-1, keepdims=True)
             + jnp.sum(p_o, axis=-1, keepdims=True))
    # A masked entry may hold anything (a slot that was never written):
    # its value is zeroed too, so that it adds exactly 0.
    g_v = jnp.where(bias.reshape(-1, 1) > 0.5 * NEG, g[:, :v_width], 0)
    o_v = jnp.where(own_bias.reshape(-1, 1) > 0.5 * NEG, own[:, :v_width], 0)
    mix = (((1,), (0,)), ((), ()))
    acc = jax.lax.dot_general(
        p_g.astype(g.dtype), g_v.astype(g.dtype), dimension_numbers=mix,
        preferred_element_type=jnp.float32,
    ) + jax.lax.dot_general(
        p_o.astype(own.dtype), o_v.astype(own.dtype), dimension_numbers=mix,
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = jnp.where(
        total > 0, acc / jnp.maximum(total, 1e-30), 0.0).astype(o_ref.dtype)
    if write:
        write_page.wait()


@functools.partial(
    jax.jit, static_argnames=("v_width", "scale", "site", "interpret"))
def sparse_attention_kernel(
    q: jnp.ndarray,  # [N, Cq, Hq, W] absorbed queries
    gathered: jnp.ndarray,  # [N, Cq, K, W] the selected rows, pool dtype
    bias: jnp.ndarray,  # [N, Cq, K] float32
    own_rows: jnp.ndarray,  # [N, Co, W] pool dtype
    own_bias: jnp.ndarray,  # [N, Cq, Co] float32
    pool_pages: jnp.ndarray | None = None,  # [1, L, 1, P, page, W]: write
    slots: jnp.ndarray | None = None,  # [N] where own_rows[:, 0] goes
    layer: jnp.ndarray | int = 0,
    *,
    v_width: int,
    scale: float,
    site: str,
    interpret: bool = False,
):
    """``o [N, Cq, Hq, v_width]`` in q's dtype — and, given ``pool_pages``
    (the decode step: ``Cq == 1``), the pool with ``own_rows[:, 0]``
    written at ``slots``."""
    N, Cq, Hq, W = q.shape
    K, Co = gathered.shape[2], own_rows.shape[1]
    dtype = gathered.dtype
    write = pool_pages is not None
    rows = -(-Hq // 16) * 16  # a whole sublane tile of either dtype
    cop = -(-Co // 16) * 16
    qk = jnp.zeros((N * Cq, rows, W), dtype).at[:, :Hq].set(
        (q.astype(jnp.float32) * scale).astype(dtype).reshape(N * Cq, Hq, W))
    own_rows = jnp.pad(own_rows.astype(dtype), ((0, 0), (0, cop - Co), (0, 0)))
    own_bias = jnp.pad(own_bias, ((0, 0), (0, 0), (0, cop - Co)),
                       constant_values=NEG)
    n_scalars = 2 if write else 0

    def per_query(*shape):
        return pl.BlockSpec((None, *shape), lambda i, *_: (i, 0, 0))

    in_specs = [
        per_query(rows, W),
        per_query(K, W),
        per_query(1, K),
        pl.BlockSpec((None, cop, W), lambda i, *_: (i // Cq, 0, 0)),
        per_query(1, cop),
    ]
    operands = [
        qk, gathered.reshape(N * Cq, K, W),
        bias.astype(jnp.float32).reshape(N * Cq, 1, K), own_rows,
        own_bias.astype(jnp.float32).reshape(N * Cq, 1, cop),
    ]
    o_spec = per_query(rows, v_width)
    o_shape = jax.ShapeDtypeStruct((N * Cq, rows, v_width), jnp.float32)
    scalars, scratch, aliases = [], [], {}
    out_specs, out_shape = o_spec, o_shape
    page = 0
    if write:
        page = pool_pages.shape[4]
        scalars = [jnp.asarray(slots, jnp.int32),
                   jnp.asarray(layer, jnp.int32).reshape(1)]
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(pool_pages)
        out_specs = [pl.BlockSpec(memory_space=pl.ANY), o_spec]
        out_shape = [jax.ShapeDtypeStruct(pool_pages.shape, dtype), o_shape]
        scratch = [pltpu.VMEM((page, W), dtype), pltpu.SemaphoreType.DMA]
        aliases = {n_scalars + len(operands) - 1: 0}
    res = pl.pallas_call(
        functools.partial(_sparse_kernel, v_width=v_width, page=page,
                          write=write),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_scalars, grid=(N * Cq,), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name=f"radixmesh_mla_sparse_{site}",
    )(*scalars, *operands)
    pool_out, out = res if write else (None, res)
    out = out[:, :Hq].reshape(N, Cq, Hq, v_width).astype(q.dtype)
    return (out, pool_out) if write else out


def _sparse_attention_ref(q, gathered, bias, own_rows, own_bias, v_width, scale):
    f32 = jnp.float32
    g, own = gathered.astype(f32), own_rows.astype(f32)
    s_g = jnp.einsum("nchw,nckw->nchk", q.astype(f32), g, precision=_PREC)
    s_o = jnp.einsum("nchw,now->ncho", q.astype(f32), own, precision=_PREC)
    s = jnp.concatenate([s_g * scale + bias[:, :, None, :],
                         s_o * scale + own_bias[:, :, None, :]], axis=-1)
    seen = jnp.concatenate([bias, own_bias], axis=-1)[:, :, None, :] > 0.5 * NEG
    p = jnp.where(seen, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    total = jnp.sum(p, axis=-1, keepdims=True)
    p = jnp.where(total > 0, p / jnp.maximum(total, 1e-30), 0.0)
    K = g.shape[2]
    g_v = jnp.where(bias[..., None] > 0.5 * NEG, g[..., :v_width], 0.0)
    o_v = own[..., :v_width]
    out = (jnp.einsum("nchk,nckv->nchv", p[..., :K], g_v, precision=_PREC)
           + jnp.einsum("ncho,nov->nchv", p[..., K:], o_v, precision=_PREC))
    return out.astype(q.dtype)


def _gather_rows(pool_pages: jnp.ndarray, layer, slots: jnp.ndarray):
    """The selected rows ``[..., W]`` of one layer of the pool
    ``[1, L, 1, P, page, W]``: one row a slot, nothing else is read."""
    with jax.named_scope("radixmesh_dsa_gather"):
        L, P, page, W = (pool_pages.shape[i] for i in (1, 3, 4, 5))
        flat = pool_pages.reshape(L * P * page, W)
        return jnp.take(flat, layer * (P * page) + slots, axis=0, mode="clip")


def sparse_attention(
    q, own_rows, sel: Selection, pool_pages, layer, v_width: int, scale: float,
    write_slots=None, *, site: str, use_kernel: bool | None = None,
    interpret: bool = False,
):
    """Attention of a group's queries ``q`` [N, Cq, Hq, W] over the rows
    ``sel`` names (gathered from ``pool_pages``) and its own rows
    [N, Co, W]: ``[N, Cq, Hq, v_width]``. With ``write_slots`` [N] (the
    decode step) ``own_rows[:, 0]`` is written there and the pool is
    returned too."""
    gathered = _gather_rows(pool_pages, layer, sel.slots)
    write = write_slots is not None
    layer = jnp.asarray(layer, jnp.int32)

    def kernel(q, gathered, bias, own_rows, own_bias, *pool):
        return sparse_attention_kernel(
            q, gathered, bias, own_rows, own_bias, *pool, v_width=v_width,
            scale=scale, site=site, interpret=interpret,
        )

    def reference(q, gathered, bias, own_rows, own_bias, *pool):
        out = _sparse_attention_ref(
            q, gathered, bias, own_rows, own_bias, v_width, scale)
        if not pool:
            return out
        pages, slots, layer = pool
        page = pages.shape[4]
        return out, pages.at[0, layer, 0, slots // page, slots % page].set(
            own_rows[:, 0])

    pool = () if not write else (
        pool_pages, jnp.asarray(write_slots, jnp.int32), layer)
    return _by_target(
        use_kernel, kernel, reference, q, gathered, sel.bias,
        own_rows.astype(pool_pages.dtype), sel.own_bias, *pool,
    )


# ---------------------------------------------------------------------------
# the decode step: B groups of one query
# ---------------------------------------------------------------------------


def decode_select(
    qi, wi, new_key, index_pages, page_table, lengths, layer, topk: int,
    use_kernel: bool | None = None, interpret: bool = False,
) -> Selection:
    """A full layer's selection for one decode step: ``qi`` [B, Hi, Di],
    ``wi`` [B, Hi], this token's key ``new_key`` [B, Di] (in the pool's
    dtype; the caller stores it), ``lengths`` [B] the context with this
    token."""
    page = index_pages.shape[2]
    prior = jnp.maximum(lengths - 1, 0)
    scores = index_scores(
        qi[:, None], wi[:, None], index_pages, page_table, prior, layer,
        site="decode", use_kernel=use_kernel, interpret=interpret,
    )
    own = own_scores(qi[:, None], wi[:, None], new_key[:, None],
                     (lengths > 0)[:, None, None])
    return select(scores, own, page_table, prior, page, topk)


def decode_attend(
    q, new_row, sel: Selection, pool_pages, slots, layer, v_width: int,
    scale: float, use_kernel: bool | None = None, interpret: bool = False,
):
    """One decode step of a sparse latent layer, write and attention
    fused: ``q`` [B, Hq, W], ``new_row`` [B, W] →
    ``(o [B, Hq, v_width], pool_pages)``."""
    out, pool_pages = sparse_attention(
        q[:, None], new_row[:, None], sel, pool_pages, layer, v_width, scale,
        write_slots=slots, site="decode", use_kernel=use_kernel,
        interpret=interpret,
    )
    return out[:, 0], pool_pages


# ---------------------------------------------------------------------------
# a prefill chunk: tiles of queries, one after another
# ---------------------------------------------------------------------------


def _tiles(C: int) -> tuple[int, int]:
    """``(queries a tile, tiles a row)`` of a chunk of ``C`` tokens."""
    tq = min(C, _TILE)
    return tq, C // tq


def _tile_of(a, b, t, tq: int):
    """Tile ``t`` of row ``b`` of ``a`` [B, C, ...], as a group of one."""
    return jax.lax.dynamic_slice_in_dim(a[b], t * tq, tq)[None]


def _own_visible(C: int, tq: int, t, prior_b, kvlen_b):
    """[1, tq, C]: which of the chunk's tokens query ``t * tq + i`` of a
    row sees — those at or before it that are real."""
    c_q = t * tq + jnp.arange(tq)[:, None]
    c_k = jnp.arange(C)[None, :]
    return ((c_k <= c_q) & (prior_b + c_k < kvlen_b))[None]


def chunk_select(
    qi, wi, own_keys, index_pages, page_table, prior_lengths, kv_lengths,
    layer, topk: int, use_kernel: bool | None = None, interpret: bool = False,
) -> Selection:
    """A full layer's selection for a prefill chunk: ``qi`` [B, C, Hi, Di],
    ``wi`` [B, C, Hi], the chunk's own keys ``own_keys`` [B, C, Di] (in
    the pool's dtype). Returns a ``Selection`` with ``N = B``, ``Cq = Co =
    C``."""
    B, C = qi.shape[:2]
    page = index_pages.shape[2]
    tq, per_row = _tiles(C)

    def tile(i):
        b, t = i // per_row, i % per_row
        q_t, w_t = _tile_of(qi, b, t, tq), _tile_of(wi, b, t, tq)
        scores = index_scores(
            q_t, w_t, index_pages, page_table[b][None],
            prior_lengths[b][None], layer, site="chunk",
            use_kernel=use_kernel, interpret=interpret,
        )
        own = own_scores(
            q_t, w_t, own_keys[b][None],
            _own_visible(C, tq, t, prior_lengths[b], kv_lengths[b]),
        )
        sel = select(scores, own, page_table[b][None],
                     prior_lengths[b][None], page, topk)
        return jax.tree_util.tree_map(lambda a: a[0], sel)

    sel = jax.lax.map(tile, jnp.arange(B * per_row))  # [B * per_row, tq, ·]
    return jax.tree_util.tree_map(
        lambda a: a.reshape(B, C, a.shape[-1]), sel)


def chunk_attend(
    q, own_rows, sel: Selection, pool_pages, layer, v_width: int, scale: float,
    use_kernel: bool | None = None, interpret: bool = False,
):
    """A chunk's queries ``q`` [B, C, Hq, W] over what ``sel`` names and
    over the chunk's own rows ``own_rows`` [B, C, W]:
    ``[B, C, Hq, v_width]``."""
    B, C = q.shape[:2]
    tq, per_row = _tiles(C)

    def tile(i):
        b, t = i // per_row, i % per_row
        out = sparse_attention(
            _tile_of(q, b, t, tq), own_rows[b][None],
            Selection(*(_tile_of(a, b, t, tq) for a in sel)),
            pool_pages, layer, v_width, scale, site="chunk",
            use_kernel=use_kernel, interpret=interpret,
        )
        return out[0]

    out = jax.lax.map(tile, jnp.arange(B * per_row))  # [B*per_row, tq, Hq, v]
    return out.reshape(B, C, *out.shape[2:])


# ---------------------------------------------------------------------------
# dense prefill (no pages): the selection as a mask
# ---------------------------------------------------------------------------


def masked_prefill_attention(q, rows, mask, v_width: int, scale: float):
    """New tokens ``q`` [B, S, Hq, W] over a dense context ``rows``
    [B, T, W] under ``mask`` [B, S, T] (causal and selected):
    ``[B, S, Hq, v_width]``. Plain ``jax.numpy``: the dense path holds at
    most ``long_prefill_threshold`` tokens."""
    f32 = jnp.float32
    r = rows.astype(f32)
    s = jnp.einsum("bshw,btw->bsht", q.astype(f32), r, precision=_PREC) * scale
    s = jnp.where(mask[:, :, None, :], s, NEG)
    p = jnp.where(mask[:, :, None, :],
                  jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    total = jnp.sum(p, axis=-1, keepdims=True)
    p = jnp.where(total > 0, p / jnp.maximum(total, 1e-30), 0.0)
    out = jnp.einsum("bsht,btv->bshv", p, r[..., :v_width], precision=_PREC)
    return out.astype(q.dtype)
