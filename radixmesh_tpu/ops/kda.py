"""Gated delta rule with a per-channel decay (KDA: Kimi Delta Attention,
arXiv:2510.26692), one matrix state a head:

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T      o_t = S_t^T q_t

``S`` [dk, dv] float32, ``a_t = exp(g_t)`` in (0, 1) a KEY channel, ``b_t``
in (0, 1) a head, ``k_t`` of unit length. Written as the program computes
it: ``S' = Diag(a) S``; ``u = S'^T (b k)``; ``S = S' + k (b v - u)^T``.
The state is laid ``[heads * dk, dv]`` — a head's 128 key channels on the
sublanes, its 128 value channels on the lanes — in the kernels, in the
state slots (``cache/kv_pool.py``) and here alike.

Forms of the one recurrence:

- :func:`kda_recurrent_ref` — ``lax.scan`` over positions: the oracle of
  everything below, and the path of every lowering target but a TPU.
- :func:`kda_blocked_ref` — the chunked (WY) form in ``jax.numpy``: a
  block's rank-one corrections solved together as one unit-triangular
  system, every decay taken as ``exp`` of a DIFFERENCE of the cumulative
  log-decay between two positions of the block (never ``1 / exp(G)``: with
  the gate at its bound of -5 a token, 64 tokens are ``exp(320)``). The
  form whose operations ``benchmarks/harness/opsbytes_kda.py`` counts.
- ``radixmesh_kda_chunk`` (:func:`kda_chunk_kernel`) — the prefill kernel:
  the chunked form on the MXU, one algorithm whatever the chunk. A grid
  step takes 8 heads of a row through a block of 32 positions (what the
  chunk allows otherwise: ``_chunk_geometry``); the heads' states stay in
  VMEM from block to block, read from the row's slot by DMA (64 KiB a
  head, contiguous) while the block's own products are computed, and the
  state after ``snap_at[b, k]`` positions (0: the state it started from) is
  left in slot ``state_out[b, k]`` by DMA, in place — an index a row, so a
  padded row's state is exact at its true end and a snapshot inside a block
  is an input, never a compiled shape (``ops/ssm.py``'s contract): there
  the block's own sum is taken again under a mask, only where a row asks.
  A block is two parts of 16. Inside a part the decay between two positions
  is the product of one's to the part's middle and the other's from it
  (exponents of at most 8 positions: exp(40) at the gate's bound), so all
  parts of 128 rows are the diagonal of one product; ``I + N`` of a part is
  inverted by forward substitution in registers (15 steps, every part of
  the step at once) and the parts are merged two by two across a cut, where
  a decay is the product of a row's from the cut and a column's up to it
  (both exponents <= 0). Every cumulative log-decay is a sum of terms of one
  sign inside a part or a half — never a difference of two long sums, never
  ``1 / exp(G)`` over more than 8 positions. Every product takes float32
  operands at ``Precision.HIGHEST`` (six MXU passes), so the kernel's
  ceiling against the bf16 peak is a sixth of what its products fill.
  Nothing is unrolled over heads, rows or positions but the 15 steps of
  the substitution: the traced body is the same for 8 heads and 32, for 16
  positions and 1 024 (``tests/test_bailing_hybrid.py`` holds it).
- ``radixmesh_kda_decode`` (:func:`kda_decode_kernel`) — one position a
  row: a program DMAs the row's state slot in, applies the same update
  and writes it back IN PLACE (the slot array is aliased), so a step
  reads and writes each live row's state once and nothing else of the
  array. Bound by HBM: 2 x 2 MiB a row and layer.
- ``radixmesh_kda_boundary`` (:func:`copy_slots`) — the boundary copies of
  a decode launch: for the rows that completed a page, slot to slot over
  every layer by DMA, HBM to HBM; rows that did not cost nothing.

``radixmesh_kda_decode`` takes the per-key-channel vectors of a position
(``a``, ``k``, ``a * b * k``, ``q``) as COLUMNS: ``cols`` [B, dk, 4 * heads],
laid by XLA (a transpose of what the projections give), because a vector
along the sublanes times the state needs no relayout in the kernel, and a
reduction over the key channels is then a sum of vregs. The chunk kernel
takes ``q``, ``k``, ``b k``, ``g`` and ``b v`` as the projections give them
([B, T, heads, d]: a block of 32 positions x 8 heads x d is a block of the
array, and a head's [32, d] tile a strided read of it): no relayout by
XLA, two products more for its fusions.

:func:`short_conv` is the depth-wise causal convolution in front (width
4, SiLU), from the last ``taps - 1`` inputs a row carries.
"""
from __future__ import annotations

# meshcheck: file-ok[timeout-audit] every wait() in this file is a
# Pallas DMA-semaphore wait inside a TPU kernel body (Mosaic lowers it to
# a hardware sync on a copy the same program started) — not a host thread
# wait; no timeout concept exists on-device.

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "kda_recurrent_ref", "kda_blocked_ref", "kda_chunk", "kda_chunk_kernel",
    "kda_decode", "kda_decode_kernel", "kda_decode_ref", "copy_slots",
    "copy_slots_kernel", "short_conv",
]

_F32 = jnp.float32


def _step(S, q, k, v, a, b):
    """One position of every head: ``S`` [B, H, dk, dv]; ``q``, ``k``,
    ``a`` [B, H, dk]; ``v`` [B, H, dv]; ``b`` [B, H] -> ``(o [B, H, dv],
    S)``."""
    S = S * a[..., None]
    u = jnp.einsum("bhkv,bhk->bhv", S, k * b[..., None],
                   precision=jax.lax.Precision.HIGHEST)
    S = S + k[..., None] * (b[..., None] * v - u)[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", S, q, precision=jax.lax.Precision.HIGHEST)
    return o, S


def kda_recurrent_ref(q, k, v, g, beta, S0, snap_at):
    """``q``, ``k``, ``g`` [B, T, H, dk] (``g`` the log-decay, <= 0); ``v``
    [B, T, H, dv]; ``beta`` [B, T, H]; ``S0`` [B, H, dk, dv]; ``snap_at``
    [B, K] -> ``(o [B, T, H, dv] float32, states [B, K, H, dk, dv])``."""
    q, k, v, g, beta, S0 = (t.astype(_F32) for t in (q, k, v, g, beta, S0))
    K = snap_at.shape[1]
    snaps0 = jnp.broadcast_to(S0[:, None], (S0.shape[0], K, *S0.shape[1:]))

    def step(carry, xs):
        S, snaps = carry
        qt, kt, vt, gt, bt, t = xs
        o, S = _step(S, qt, kt, vt, jnp.exp(gt), bt)
        hit = (snap_at == t + 1)[:, :, None, None, None]
        return (S, jnp.where(hit, S[:, None], snaps)), o

    T = q.shape[1]
    (_, snaps), o = jax.lax.scan(
        step, (S0, snaps0),
        (*(t.swapaxes(0, 1) for t in (q, k, v, g, beta)), jnp.arange(T)))
    return o.swapaxes(0, 1), snaps


def kda_blocked_ref(q, k, v, g, beta, S0, block: int = 64):
    """:func:`kda_recurrent_ref` in the chunked form, blocks of ``block``
    positions (``T`` a multiple): ``(o [B, T, H, dv], S_T [B, H, dk, dv])``.
    With ``G`` the cumulative log-decay inside a block (inclusive), ``U``
    solves ``(I + Diag(b) tril(A, -1)) U = Diag(b) (V - (exp(G) k) S_0)``
    where ``A[t, s] = sum_i k_t[i] k_s[i] exp(G_t[i] - G_s[i])``; then
    ``o_t = (exp(G_t) q_t) S_0 + sum_{s <= t} Q[t, s] u_s`` (``Q`` as ``A``
    with ``q_t``) and ``S_C = Diag(exp(G_C)) S_0 + sum_s (exp(G_C - G_s)
    k_s) u_s^T``. Every exponent is a difference <= 0."""
    hi = jax.lax.Precision.HIGHEST
    q, k, v, g, beta, S = (t.astype(_F32) for t in (q, k, v, g, beta, S0))
    B, T, H, dk = q.shape
    if T % block:
        raise ValueError(f"positions {T} must be a multiple of {block}")
    C = block
    lower = jnp.tril(jnp.ones((C, C), bool), -1)
    causal = jnp.tril(jnp.ones((C, C), bool))
    outs = []
    for c0 in range(0, T, C):
        qc, kc, vc, gc, bc = (t[:, c0:c0 + C] for t in (q, k, v, g, beta))
        G = jnp.cumsum(gc, axis=1)  # [B, C, H, dk]
        # [B, H, C(t), C(s), dk]: exp of a difference, masked to s <= t
        diff = G[:, :, None] - G[:, None, :]  # [B, t, s, H, dk]
        decay = jnp.where(causal[None, :, :, None, None],
                          jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
        A = jnp.einsum("bthi,bshi,btshi->bhts", kc, kc, decay, precision=hi)
        Q = jnp.einsum("bthi,bshi,btshi->bhts", qc, kc, decay, precision=hi)
        bh = bc.transpose(0, 2, 1)  # [B, H, C]
        rhs = bh[..., None] * (
            vc.transpose(0, 2, 1, 3)
            - jnp.einsum("bthi,bhiv->bhtv", jnp.exp(G) * kc, S, precision=hi))
        system = jnp.eye(C) + bh[..., None] * jnp.where(lower, A, 0.0)
        U = jax.scipy.linalg.solve_triangular(
            system, rhs, lower=True, unit_diagonal=True)  # [B, H, C, dv]
        o = (jnp.einsum("bthi,bhiv->bthv", jnp.exp(G) * qc, S, precision=hi)
             + jnp.einsum("bhts,bhsv->bthv", jnp.where(causal, Q, 0.0), U,
                          precision=hi))
        outs.append(o)
        tail = jnp.exp(G[:, -1:] - G) * kc  # [B, C, H, dk]
        S = (jnp.exp(G[:, -1])[..., None] * S
             + jnp.einsum("bshi,bhsv->bhiv", tail, U, precision=hi))
    return jnp.concatenate(outs, axis=1), S


# ---------------------------------------------------------------------------
# what the kernels take
# ---------------------------------------------------------------------------


def _columns(q, k, g, beta):
    """``cols`` [B, T, dk, 4 * H] float32 of ``q``, ``k``, ``g`` [B, T, H,
    dk] and ``beta`` [B, T, H]: lane ``j * H + h`` holds head ``h``'s
    ``a = exp(g)`` (j 0), ``k`` (1), ``a * beta * k`` (2: what ``u`` takes
    against the state BEFORE its decay) and ``q`` (3)."""
    a = jnp.exp(g.astype(_F32))
    k = k.astype(_F32)
    parts = jnp.stack(
        [a, k, a * beta.astype(_F32)[..., None] * k, q.astype(_F32)], axis=2)
    B, T, _, H, dk = parts.shape  # [B, T, 4, H, dk]
    return parts.reshape(B, T, 4 * H, dk).swapaxes(2, 3)


def _update_heads(cols, bv_row, s_load, s_store, o_store, n_heads: int, dk: int):
    """One position of every head on the vector unit. ``cols`` [dk, 4H];
    ``bv_row(h)`` -> ``beta * v`` [1, dv]; the state of head ``h`` is read
    by ``s_load(h)`` [dk, dv] and written by ``s_store(h, S)``; its output
    [1, dv] goes to ``o_store(h, o)``."""
    H = n_heads
    for h in range(H):
        a = cols[:, h:h + 1]
        kk = cols[:, H + h:H + h + 1]
        abk = cols[:, 2 * H + h:2 * H + h + 1]
        qq = cols[:, 3 * H + h:3 * H + h + 1]
        S = s_load(h)
        # u = (Diag(a) S)^T (b k) = S^T (a b k): one pass over S, before
        # the decay is applied
        u = jnp.sum(S * abk, axis=0, keepdims=True)
        S = S * a + kk * (bv_row(h) - u)
        s_store(h, S)
        o_store(h, jnp.sum(S * qq, axis=0, keepdims=True))


# Positions of a diagonal part, positions and heads of a grid step at most.
# On a v5e at 32 heads of 128 (PERF.md, PR 50): 0.69 us a token and layer at
# 32 x 8, 0.74 at 64 x 8, 0.70 at 16 x 16, 0.94 at 64 x 2 — a step is a
# chain of products each waiting for the one before, and heads are what
# runs beside it.
_SUB, _BLOCK, _HEADS_A_STEP = 16, 32, 8
_HI = jax.lax.Precision.HIGHEST


def _chunk_geometry(T: int, H: int) -> tuple[int, int, int]:
    """``(s, n, hb)`` of a chunk of ``T`` positions and ``H`` heads: a grid
    step takes ``hb`` heads through a block of ``n`` parts of ``s``
    positions — 2 x 16 where ``T`` allows, what ``T`` allows otherwise (16
    or 48: one part of 16 a block; 8: one part of 8)."""
    s = min(T, _SUB)
    if T % s or s & (s - 1):
        raise ValueError(f"positions {T} must be a multiple of {_SUB}, or "
                         f"a power of two below it")
    return s, math.gcd(T // s, _BLOCK // _SUB), math.gcd(H, _HEADS_A_STEP)


def _mm(spec, a, b):
    """A product of float32 operands at full precision (six passes of the
    MXU): a bf16 pass is a lower precision than the state's."""
    return jnp.einsum(spec, a, b, precision=_HI, preferred_element_type=_F32)


def _chunk_kernel(snap_ref, layer_ref, in_ref, fresh_ref, keep_ref, q_ref,
                  k_ref, kb_ref, g_ref, bv_ref, pool_in, o_ref, pool_ref,
                  s_scr, snap_scr, sem, *, s: int, n: int, hb: int,
                  n_snaps: int, dk: int, dv: int):
    """A block of ``C = n * s`` positions of ``hb`` heads of one row (module
    docstring): grid ``(rows, heads / hb, T / C)``, positions innermost, the
    heads' state in ``s_scr`` from block to block."""
    del pool_in  # aliased to pool_ref
    row, h_blk, t_blk = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    C, t0 = n * s, t_blk * (n * s)
    slots = pool_ref.at[layer_ref[0]]

    def mine(slot):
        """The step's ``hb`` heads of a slot: ``[hb * dk, dv]``, contiguous."""
        return slots.at[slot, pl.ds(h_blk * (hb * dk), hb * dk)]

    def keep(j, src):
        """``src`` [hb * dk, dv] to the ``j``-th slot this row keeps to."""
        dma = pltpu.make_async_copy(src, mine(keep_ref[row * n_snaps + j]), sem)
        dma.start()
        dma.wait()

    def wanted(j, at):
        """Does the row keep its state after ``at`` positions in its
        ``j``-th slot (slot 0: nobody does)?"""
        return (keep_ref[row * n_snaps + j] != 0) & at(
            snap_ref[row * n_snaps + j])

    def keep_where(at, src):
        for j in range(n_snaps):
            pl.when(wanted(j, at))(functools.partial(keep, j, src))

    # the state the row starts from is on its way while everything that
    # does not need it is computed
    resumes = (t_blk == 0) & (fresh_ref[row] == 0)
    load = pltpu.make_async_copy(mine(in_ref[row]), s_scr, sem)
    pl.when(resumes)(load.start)

    @pl.when((t_blk == 0) & (fresh_ref[row] != 0))
    def _zero():
        s_scr[...] = jnp.zeros_like(s_scr)

    def heads(ref):
        """A block ``[C, hb, d]`` — as the projections lay it: a position's
        heads on the sublanes — as ``[hb, C, d]``: a strided read a head."""
        return jnp.stack([ref[:, i, :] for i in range(hb)])

    def of_row(x, size, at):
        """Row ``at`` of every ``size`` rows of ``x`` [hb, C, d], to all of
        them."""
        x = x.reshape(hb, C // size, size, dk)[:, :, at:at + 1]
        return jnp.broadcast_to(x, (hb, C // size, size, dk)).reshape(hb, C, dk)

    def column(x):
        """Rows ``[hb, 1, dk]`` as columns ``[hb, dk, 1]`` (a masked sum
        over the lanes: no transpose)."""
        eye = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1))
        return jnp.sum(jnp.where(eye, x, 0.0), axis=2, keepdims=True)

    q, k, kb, g = (heads(ref) for ref in (q_ref, k_ref, kb_ref, g_ref))
    pos = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)

    def widen(cum, rest, size):
        """``cum`` (the log-decay from a group's first position to each of
        its positions, inclusive) and ``rest`` (after a position to its
        group's end) of groups of ``size`` as those of ``2 * size``: a
        half's whole sum — its last row's — to the other half's rows. Sums
        of one sign throughout: no decay below is ``exp`` of a difference
        of two long sums (after 32 positions at the gate's bound a sum is
        -160 and its float32 step 1.5e-5)."""
        right = pos & size != 0
        return (
            jnp.where(right, cum + of_row(cum, 2 * size, size - 1), cum),
            jnp.where(right, rest, rest + of_row(cum, 2 * size, 2 * size - 1)))

    half = max(s // 2, 1)
    at = pos & (half - 1)
    cum, shift = g, 1
    rest = jnp.where(at < half - 1, pltpu.roll(g, C - 1, 1), 0.0)
    while shift < half:
        cum = cum + jnp.where(at >= shift, pltpu.roll(cum, shift, 1), 0.0)
        rest = rest + jnp.where(
            at < half - shift, pltpu.roll(rest, C - shift, 1), 0.0)
        shift *= 2

    # -- the diagonal parts, ``s`` positions each: the decay between two
    # positions of a part is the product of one's to the part's MIDDLE and
    # the other's from it — one of the two a positive exponent, but of at
    # most 8 positions (exp(40) at the gate's bound) — so the parts of 128
    # rows (four heads of a block of 32) are the blocks on the diagonal of
    # ONE product; what lies off it is not used
    to_mid = jnp.where(pos & half != 0, cum, -rest) if s > 1 else cum
    f_row, f_col = jnp.exp(to_mid), jnp.exp(-to_mid)
    Wd = min(hb * C, 128)
    P, Gw = hb * C // Wd, Wd // s
    both = _mm(
        "ptd,pcd->ptc",
        jnp.concatenate([(kb * f_row).reshape(P, Wd, dk),
                         (q * f_row).reshape(P, Wd, dk)], axis=1),
        (k * f_col).reshape(P, Wd, dk)).reshape(P, 2 * Gw, s, Wd)
    # packed: row t of every part side by side, [P, s, (part, column)] (the
    # sizes are powers of two: a group is what two indices share above it)
    iota4 = functools.partial(
        jax.lax.broadcasted_iota, jnp.int32, (P, 2 * Gw, s, Wd))
    packed = jnp.where(
        iota4(3) ^ (iota4(1) * s & (Wd - 1)) < s, both, 0.0,
    ).reshape(P, 2, Gw, s, Wd).sum(axis=2)
    t_id = jax.lax.broadcasted_iota(jnp.int32, (s, Wd), 0)
    c_id = jax.lax.broadcasted_iota(jnp.int32, (s, Wd), 1) & (s - 1)
    n_p = jnp.where(t_id > c_id, packed[:, 0], 0.0)
    # column c of every part's N = Diag(b) tril(A, -1), to all the lanes of
    # its part (a product with blocks of ones: exact), then the forward
    # substitution that inverts I + N by columns — row t loses N[t, c]
    # times row c — in registers, two vregs a 128 rows
    spread = jax.lax.broadcasted_iota(jnp.int32, (s, s, Wd), 0)
    same_part = (jax.lax.broadcasted_iota(jnp.int32, (Wd, Wd), 0)
                 ^ jax.lax.broadcasted_iota(jnp.int32, (Wd, Wd), 1)) < s
    cols = _mm(
        "rw,wv->rv",
        jnp.where(c_id == spread, n_p[:, None], 0.0).reshape(P * s * s, Wd),
        jnp.where(same_part, 1.0, 0.0)).reshape(P, s, s, Wd)
    x = jnp.broadcast_to(jnp.where(t_id == c_id, 1.0, 0.0), (P, s, Wd))
    for c in range(s - 1):
        x = x - cols[:, c] * x[:, c:c + 1]

    def unpacked(x):
        """``[P, s, (part, column)]`` as the parts on the diagonal of
        ``[hb, C, C]``."""
        x = jnp.where(
            same_part,
            jnp.broadcast_to(x[:, None], (P, Gw, s, Wd)).reshape(P, Wd, Wd),
            0.0)
        if Wd == C:
            return x
        return jnp.stack([x[:, i * C:(i + 1) * C, i * C:(i + 1) * C]
                          for i in range(Wd // C)], axis=1).reshape(hb, C, C)

    inv = unpacked(x)
    qm = unpacked(jnp.where(t_id >= c_id, packed[:, 1], 0.0))
    if s > 1:
        cum, rest = widen(cum, rest, half)

    # -- the parts merged two by two: across a cut, a decay is the product
    # of the row's from the cut and the column's up to it (both <= 0 as
    # exponents), so the off-diagonal parts are matrix products, and
    # ``[[A, 0], [N, D]]^-1 = X - X [[0, 0], [N, 0]] X`` with ``X`` the
    # halves' inverses on the diagonal
    r_id = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    c_id = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    size = s
    while size < C:
        f = jnp.exp(jnp.where(pos & size != 0, cum, rest))
        m = _mm("htd,hsd->hts",
                jnp.concatenate([kb * f, q * f], axis=1), k * f)
        # a row of a right half and a column of the left half beside it
        pair = ((r_id ^ c_id) & -size == size) & (r_id & size != 0)
        qm = qm + jnp.where(pair, m[:, C:], 0.0)
        inv = inv - _mm("hts,hsu->htu", inv, _mm(
            "hts,hsu->htu", jnp.where(pair, m[:, :C], 0.0), inv))
        cum, rest = widen(cum, rest, size)
        size *= 2

    # -- the block against the state it started from
    @pl.when(resumes)
    def _arrived():
        load.wait()

    keep_where(lambda p: (t_blk == 0) & (p == 0), s_scr)  # as it started
    S0 = s_scr[...].reshape(hb, dk, dv)
    f_in = jnp.exp(cum)
    met = _mm("htk,hkv->htv",
              jnp.concatenate([kb * f_in, q * f_in], axis=1), S0)
    u = _mm("hts,hsv->htv", inv, heads(bv_ref) - met[:, :C])
    o = met[:, C:] + _mm("hts,hsv->htv", qm, u)
    for i in range(hb):
        o_ref[:, i, :] = o[i]

    def state_after(decay_to, decay_all):
        """``Diag(decay_all) S_0 + sum_s (decay_to_s k_s) u_s^T``."""
        return (column(decay_all) * S0 + _mm(
            "hsk,hsv->hkv", k * decay_to, u)).reshape(hb * dk, dv)

    # a state asked for INSIDE the block: the same sum, masked at the
    # row's position (its decays from a masked sum of g: one sign again)
    for j in range(n_snaps):
        @pl.when(wanted(j, lambda p: (p > t0) & (p < t0 + C)))
        def _inside(j=j):
            p = snap_ref[row * n_snaps + j] - t0
            between = jnp.where((c_id > r_id) & (c_id < p), 1.0, 0.0)
            to_p = _mm("ts,hsd->htd", between, g)
            upto = jnp.sum(jnp.where(pos < p, g, 0.0), axis=1, keepdims=True)
            snap_scr[...] = state_after(
                jnp.where(pos < p, jnp.exp(to_p), 0.0), jnp.exp(upto))
            keep(j, snap_scr)

    s_scr[...] = state_after(jnp.exp(rest), jnp.exp(cum[:, C - 1:]))
    keep_where(lambda p: p == t0 + C, s_scr)


# Rows a call of ``radixmesh_kda_chunk``. A launch of 32 rows x 64 positions
# that held ONE call over all its rows and ``radixmesh_mla_chunk`` never
# returned on the chip (16 rows did; either kernel alone did at 32; the
# values did not matter; this kernel asking for no VMEM of its own did not
# help): the compiler keeps the call's 32 MiB of outputs in VMEM (``S(1)``)
# while the latent kernel, which asks for 64 MiB, is told it owns VMEM from
# offset 0 (PERF.md: PR 43 met the same with a window-group launch). Calls
# of 16 rows keep each output at what was seen to work (PERF.md, PR 48).
_ROWS_A_CALL = 16


def _chunk_call(seqs, pool, layer, state_in, fresh, snap_at, state_out,
                interpret: bool):
    """``radixmesh_kda_chunk`` over the rows given (``seqs``: q, k, b k, g
    and b v, each [B, T, H, d]): ``(o [B, T, H, dv], pool)``."""
    B, T, H, dk = seqs[0].shape
    dv = seqs[4].shape[3]
    K = snap_at.shape[1]
    s, n, hb = _chunk_geometry(T, H)
    C = s * n

    def seq(d):
        return pl.BlockSpec((None, C, hb, d), lambda r, h, t, *_: (r, t, h, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, H // hb, T // C),
        in_specs=[seq(dk), seq(dk), seq(dk), seq(dk), seq(dv),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[seq(dv), pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[pltpu.VMEM((hb * dk, dv), _F32),
                        pltpu.VMEM((hb * dk, dv), _F32),
                        pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        functools.partial(_chunk_kernel, s=s, n=n, hb=hb, n_snaps=K, dk=dk,
                          dv=dv),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, T, H, dv), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands: snap_at, layer, state_in, fresh, state_out, q, k, b k,
        # g, b v, pool
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            has_side_effects=True),
        interpret=interpret,
        name="radixmesh_kda_chunk",
    )(snap_at.reshape(-1), layer, state_in, fresh, state_out.reshape(-1),
      *seqs, pool)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(5,))
def kda_chunk_kernel(q, k, v, g, beta, pool, layer, state_in, fresh, snap_at,
                     state_out, interpret: bool = False):
    """A chunk through the rows' state slots as the Pallas kernel
    ``radixmesh_kda_chunk`` (module docstring): row ``b`` starts from
    ``pool[layer, state_in[b]]`` [H * dk, dv], or from zeros where
    ``fresh[b]``, and its state after ``snap_at[b, j]`` positions is left in
    ``pool[layer, state_out[b, j]]`` (slot 0: not kept). The kernel moves
    the states itself, slot to VMEM and VMEM to slot by DMA, in place (the
    slot array is donated and aliased): an XLA gather or scatter of rows of
    a 3.6 GiB array is lowered through slices of the WHOLE array. Returns
    ``(o [B, T, H, dv] float32, pool)``. ``_ROWS_A_CALL`` rows a call. A
    slot one row keeps to must not be the one another row of the launch
    starts from."""
    B = q.shape[0]
    b = beta.astype(_F32)[..., None]
    k = k.astype(_F32)
    # as the projections give them: no relayout, two products folded into
    # what makes k and v
    seqs = tuple(t.astype(_F32) for t in (q, k, b * k, g, b * v.astype(_F32)))
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    rows = (state_in.astype(jnp.int32), fresh.astype(jnp.int32),
            snap_at.astype(jnp.int32), state_out.astype(jnp.int32))
    outs = []
    for r0 in range(0, B, _ROWS_A_CALL):
        sl = slice(r0, r0 + _ROWS_A_CALL)
        o, pool = _chunk_call(tuple(t[sl] for t in seqs), pool, layer,
                              *(t[sl] for t in rows), interpret)
        outs.append(o)
    return (outs[0] if len(outs) == 1 else jnp.concatenate(outs)), pool


def _chunk_ref(q, k, v, g, beta, pool, layer, state_in, fresh, snap_at,
               state_out):
    """:func:`kda_chunk_kernel` as a gather, :func:`kda_recurrent_ref` and
    a scatter (every state, wanted or not: slot 0 is nobody's)."""
    B, _, H, dk = q.shape
    dv = v.shape[-1]
    S0 = jnp.where(fresh[:, None, None] != 0, 0.0, pool[layer, state_in])
    o, snaps = kda_recurrent_ref(
        q, k, v, g, beta, S0.reshape(B, H, dk, dv), snap_at)
    return o, pool.at[layer, state_out.reshape(-1)].set(
        snaps.reshape(-1, H * dk, dv))


def kda_chunk(q, k, v, g, beta, pool, layer, state_in, fresh, snap_at,
              state_out, use_kernel: bool | None = None,
              interpret: bool = False):
    """A chunk through the rows' state slots (``pool`` [layers, slots, H *
    dk, dv] float32; row ``b`` from ``pool[layer, state_in[b]]``, or from
    zeros where ``fresh[b]``; its state after ``snap_at[b, j]`` positions
    to ``pool[layer, state_out[b, j]]``) -> ``(o [B, T, H, dv] float32,
    pool)`` by lowering target (``ops/attention.py::_dispatch``'s rule):
    the kernel where the computation is lowered for a TPU, the ``lax.scan``
    anywhere else; an explicit ``use_kernel`` is honoured."""
    args = (q, k, v, g, beta, pool, jnp.asarray(layer, jnp.int32),
            state_in.astype(jnp.int32), fresh.astype(jnp.int32), snap_at,
            state_out.astype(jnp.int32))
    if use_kernel is None:
        return jax.lax.platform_dependent(
            *args, tpu=kda_chunk_kernel, default=_chunk_ref)
    if use_kernel:
        return kda_chunk_kernel(*args, interpret=interpret)
    return _chunk_ref(*args)


# ---------------------------------------------------------------------------
# one position a row, state slots in place
# ---------------------------------------------------------------------------


def _decode_kernel(layer_ref, slot_ref, cols_ref, bv_ref, s_ref, o_ref, out_ref,
                   *, n_heads: int, dk: int, dv: int):
    del layer_ref, slot_ref  # the index maps read them

    def s_store(h, S):
        out_ref[h * dk:(h + 1) * dk, :] = S

    def o_store(h, o):
        o_ref[h:h + 1, :] = o

    _update_heads(
        cols_ref[...], lambda h: bv_ref[h:h + 1, :],
        lambda h: s_ref[h * dk:(h + 1) * dk, :], s_store, o_store, n_heads, dk)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(5,))
def kda_decode_kernel(q, k, v, g, beta, pool, layer, slots,
                      interpret: bool = False):
    """One position a row as the Pallas kernel ``radixmesh_kda_decode``:
    ``q``, ``k``, ``g`` [B, H, dk], ``v`` [B, H, dv], ``beta`` [B, H];
    ``pool`` [layers, slots, H * dk, dv] float32 (donated, aliased); row
    ``b``'s state is read from ``pool[layer, slots[b]]`` and the new one
    written back there. Returns ``(o [B, H, dv] float32, pool)``. Rows
    that name the same slot (idle rows: slot 0) leave any of their states
    there."""
    B, H, dk = q.shape
    dv = v.shape[-1]
    cols = _columns(q[:, None], k[:, None], g[:, None], beta[:, None])[:, 0]
    bv = beta.astype(_F32)[..., None] * v.astype(_F32)
    row = pl.BlockSpec((None, H, dv), lambda b, *_: (b, 0, 0))
    state = pl.BlockSpec((None, None, H * dk, dv),
                         lambda b, layer, slot: (layer[0], slot[b], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, dk, 4 * H), lambda b, *_: (b, 0, 0)),
            row,
            state,
        ],
        out_specs=[row, state],
    )
    o, pool = pl.pallas_call(
        functools.partial(_decode_kernel, n_heads=H, dk=dk, dv=dv),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands: layer, slots, cols, bv, pool -> pool is input 4, output 1
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="radixmesh_kda_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      cols, bv, pool)
    return o, pool


def kda_decode_ref(q, k, v, g, beta, pool, layer, slots):
    """:func:`kda_decode_kernel` in ``jax.numpy``: a gather of the rows'
    slots, the update, a scatter back."""
    B, H, dk = q.shape
    dv = v.shape[-1]
    S = pool[layer, slots].reshape(B, H, dk, dv)
    o, S = _step(S, *(t.astype(_F32) for t in (q, k, v)),
                 jnp.exp(g.astype(_F32)), beta.astype(_F32))
    return o, pool.at[layer, slots].set(S.reshape(B, H * dk, dv))


def kda_decode(q, k, v, g, beta, pool, layer, slots,
               use_kernel: bool | None = None, interpret: bool = False):
    """One position a row through the state slots, by lowering target."""
    args = (q, k, v, g, beta, pool, jnp.asarray(layer, jnp.int32), slots)
    if use_kernel is None:
        return jax.lax.platform_dependent(
            *args, tpu=kda_decode_kernel, default=kda_decode_ref)
    if use_kernel:
        return kda_decode_kernel(*args, interpret=interpret)
    return kda_decode_ref(*args)


# ---------------------------------------------------------------------------
# boundary copies
# ---------------------------------------------------------------------------


def _copy_kernel(src_ref, dst_ref, pool_ref, out_ref, sem):
    del pool_ref  # aliased to out_ref
    b = pl.program_id(0)

    @pl.when(dst_ref[b] != 0)
    def _copy():
        dma = pltpu.make_async_copy(
            out_ref.at[:, src_ref[b]], out_ref.at[:, dst_ref[b]], sem)
        dma.start()
        dma.wait()


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def copy_slots_kernel(pool, src, dst, interpret: bool = False):
    """``pool[:, dst[b]] = pool[:, src[b]]`` for every ``b`` with ``dst[b]
    != 0``, in place, as the Pallas kernel ``radixmesh_kda_boundary``: one
    DMA a row that wants one, HBM to HBM over every layer; the others move
    nothing."""
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(src.shape[0],),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), has_side_effects=True),
        interpret=interpret,
        name="radixmesh_kda_boundary",
    )(src.astype(jnp.int32), dst.astype(jnp.int32), pool)


def _copy_slots_ref(pool, src, dst):
    """Every row's copy, wanted or not: slot 0 is nobody's."""
    return pool.at[:, dst].set(pool[:, src])


def copy_slots(pool, src, dst, use_kernel: bool | None = None,
               interpret: bool = False):
    """The boundary copies of a decode launch (module docstring)."""
    if use_kernel is None:
        return jax.lax.platform_dependent(
            pool, src, dst, tpu=copy_slots_kernel, default=_copy_slots_ref)
    if use_kernel:
        return copy_slots_kernel(pool, src, dst, interpret=interpret)
    return _copy_slots_ref(pool, src, dst)


# ---------------------------------------------------------------------------
# the convolution in front
# ---------------------------------------------------------------------------


def short_conv(x, w, state, snap_at, bias=None):
    """Depth-wise causal convolution (plus ``bias`` [C], if any) and SiLU:
    ``x`` [B, T, C], ``w`` [taps, C] (tap 0 the oldest position), ``state``
    [B, taps - 1, C] the
    inputs before the first -> ``(silu(conv) [B, T, C] float32, states [B,
    K, taps - 1, C])``: the inputs before position ``snap_at`` [B, K] of
    these ``T`` (0 hands back ``state``). ``snap_at`` None: the one state
    after all ``T`` (a decode step's: a slice, no gather)."""
    taps, T = w.shape[0], x.shape[1]
    with jax.named_scope("radixmesh_kda_conv"):
        xs = jnp.concatenate([state.astype(x.dtype), x], axis=1)
        w = w.astype(_F32)
        y = sum(w[j] * xs[:, j:j + T].astype(_F32) for j in range(taps))
        if bias is not None:
            y = y + bias.astype(_F32)
        y = jax.nn.silu(y)
        if snap_at is None:
            return y, xs[:, None, T:]
        at = snap_at[:, :, None] + jnp.arange(taps - 1)  # [B, K, taps - 1]
        return y, jax.vmap(lambda rows, ix: rows[ix])(xs, at)
