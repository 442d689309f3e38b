"""Mamba-2 (state-space duality, SSD; arXiv:2405.21060), one matrix state a
head:

    S_t = a_t S_{t-1} + (D_t u_t) B_t^T          y_t = S_t C_t

``S`` [P, N] float32 a head (``P`` the head's channels, ``N`` the state
size), ``a_t = exp(g_t)`` ONE scalar a head and position (``g = -exp(A_log)
* Delta`` <= 0), ``B_t`` and ``C_t`` [N] shared by the heads of a GROUP.
The gated linear-attention recurrence without a delta rule's correction:
``ops/kda.py``'s contract (``state_in``, ``fresh``, ``snap_at``,
``state_out``, ``layer``, a slot array aliased in place) and none of its
triangular solve. Everything here takes ``x = Delta * u`` (the input as the
state takes it); the skip ``D u``, the gate and the norm are the caller's.

A state slot holds a layer's heads as ``[heads / hp * N, hp * P]`` with
``hp = 128 / P`` heads side by side on the lanes (:func:`pack_state`): the
state size runs down the sublanes, so a position's output is a sum over
them — a row, as the projections lay it — and its update the product of a
column (``B``) and a row (``x``). 64 heads of 64 x 128: ``[4096, 128]``
float32, 2 MiB a layer.

Forms of the one recurrence:

- :func:`ssd_recurrent_ref` — ``lax.scan`` over positions: the oracle, and
  the path of every lowering target but a TPU.
- :func:`ssd_blocked_ref` — the chunked form in ``jax.numpy``, blocks of 128
  positions (the published ``chunk_size``): with ``cum`` the cumulative
  log-decay inside a block (inclusive), ``y_t = exp(cum_t) S_0 C_t + sum_{s
  <= t} exp(cum_t - cum_s) (C_t . B_s) x_s`` and ``S_L = exp(cum_L) S_0 +
  sum_s exp(cum_L - cum_s) x_s B_s^T``. Every decay is ``exp`` of a
  difference ``cum_i - cum_j`` with ``i >= j``: never positive. The form
  whose operations ``benchmarks/harness/opsbytes_ssd.py`` counts.
- ``radixmesh_ssd_chunk`` (:func:`ssd_chunk_kernel`) — the prefill kernel:
  that form on the MXU. A grid step takes the heads of ONE group of a row
  through a block of 128 positions (what a shorter chunk allows otherwise):
  ``C B^T`` once for the group, then a head's masked ``(L o C B^T) x`` and
  the state's in- and out-products, all plain matrix products of float32
  operands at ``Precision.HIGHEST``. The group's states stay in VMEM from
  block to block, read from the row's slot by DMA and left in slot
  ``state_out[b, k]`` after ``snap_at[b, k]`` positions (0: the state it
  started from) by DMA, in place; a snapshot INSIDE a block is the block's
  state sum under a mask, taken only where a row asks. The in-block
  cumulative sums are XLA's (a few numbers a position), in two parts whose
  differences stay exact behind a run of large decays (``_two_sums``).
  Nothing in the trace is unrolled over rows, groups or positions, and over
  heads only the ``heads / groups`` of one group (8 published): the traced
  body is the same for 8 heads and 64.
- ``radixmesh_ssd_decode`` (:func:`ssd_decode_kernel`) — one position a row:
  a program takes the row's slot in, applies the update and writes it back
  IN PLACE (the slot array is aliased). Bound by HBM: 2 x 2 MiB a row and
  layer.

The boundary copies and the convolution in front are ``ops/kda.py``'s
(``copy_slots``, ``short_conv`` with a bias).
"""
from __future__ import annotations

# meshcheck: file-ok[timeout-audit] every wait() in this file is a
# Pallas DMA-semaphore wait inside a TPU kernel body (Mosaic lowers it to
# a hardware sync on a copy the same program started) — not a host thread
# wait; no timeout concept exists on-device.

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "pack_state", "unpack_state", "ssd_recurrent_ref", "ssd_blocked_ref",
    "ssd_chunk", "ssd_chunk_kernel", "ssd_decode", "ssd_decode_kernel",
    "ssd_decode_ref", "CHUNK",
]

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
# Positions of a block of the chunked form: the published ``chunk_size``.
CHUNK = 128


def pack_state(S: jnp.ndarray) -> jnp.ndarray:
    """``[..., H, P, N]`` as a state slot holds it: ``[..., H / hp * N, hp *
    P]``, ``hp = 128 / P`` heads side by side (module docstring)."""
    *lead, H, P, N = S.shape
    hp = 128 // P
    S = jnp.moveaxis(S.reshape(*lead, H // hp, hp, P, N), -1, -3)
    return S.reshape(*lead, H // hp * N, hp * P)


def unpack_state(slot: jnp.ndarray, H: int, P: int) -> jnp.ndarray:
    """:func:`pack_state`'s inverse: ``[..., H / hp * N, 128]`` -> ``[..., H,
    P, N]``."""
    hp = 128 // P
    lead, N = slot.shape[:-2], slot.shape[-2] // (H // hp)
    S = jnp.moveaxis(slot.reshape(*lead, H // hp, N, hp, P), -3, -1)
    return S.reshape(*lead, H, P, N)


def _of_heads(t: jnp.ndarray, H: int) -> jnp.ndarray:
    """A group's ``[..., G, N]`` to each of its heads: ``[..., H, N]``."""
    return jnp.repeat(t, H // t.shape[-2], axis=-2)


def _step(S, x, a, Bh, Ch):
    """One position of every head: ``S`` [B, H, P, N]; ``x`` [B, H, P];
    ``a`` [B, H]; ``Bh``, ``Ch`` [B, H, N] -> ``(y [B, H, P], S)``."""
    S = S * a[..., None, None] + x[..., None] * Bh[..., None, :]
    return jnp.einsum("bhpn,bhn->bhp", S, Ch, precision=_HI), S


def ssd_recurrent_ref(x, g, Bm, Cm, S0, snap_at):
    """``x`` [B, T, H, P] (``Delta * u``); ``g`` [B, T, H] (the log-decay,
    <= 0); ``Bm``, ``Cm`` [B, T, G, N]; ``S0`` [B, H, P, N]; ``snap_at`` [B,
    K] -> ``(y [B, T, H, P] float32, states [B, K, H, P, N])``."""
    x, g, Bm, Cm, S0 = (t.astype(_F32) for t in (x, g, Bm, Cm, S0))
    H, K = x.shape[2], snap_at.shape[1]
    snaps0 = jnp.broadcast_to(S0[:, None], (S0.shape[0], K, *S0.shape[1:]))

    def step(carry, xs):
        S, snaps = carry
        xt, gt, bt, ct, t = xs
        y, S = _step(S, xt, jnp.exp(gt), _of_heads(bt, H), _of_heads(ct, H))
        hit = (snap_at == t + 1)[:, :, None, None, None]
        return (S, jnp.where(hit, S[:, None], snaps)), y

    T = x.shape[1]
    (_, snaps), y = jax.lax.scan(
        step, (S0, snaps0),
        (*(t.swapaxes(0, 1) for t in (x, g, Bm, Cm)), jnp.arange(T)))
    return y.swapaxes(0, 1), snaps


def ssd_blocked_ref(x, g, Bm, Cm, S0, block: int = CHUNK):
    """:func:`ssd_recurrent_ref` in the chunked form, blocks of ``block``
    positions (``T`` a multiple): ``(y [B, T, H, P], S_T [B, H, P, N])``
    (module docstring). Every exponent is a difference <= 0."""
    x, g, Bm, Cm, S = (t.astype(_F32) for t in (x, g, Bm, Cm, S0))
    B, T, H, P = x.shape
    if T % block:
        raise ValueError(f"positions {T} must be a multiple of {block}")
    causal = jnp.tril(jnp.ones((block, block), bool))
    outs = []
    for c0 in range(0, T, block):
        xc, gc, bc, cc = (t[:, c0:c0 + block] for t in (x, g, Bm, Cm))
        bh, ch = _of_heads(bc, H), _of_heads(cc, H)
        cum = jnp.cumsum(gc, axis=1)  # [B, L, H]
        diff = cum[:, :, None] - cum[:, None, :]  # [B, t, s, H]
        decay = jnp.where(causal[None, :, :, None],
                          jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
        cb = jnp.einsum("bthn,bshn->btsh", ch, bh, precision=_HI)
        y = (jnp.einsum("btsh,bshp->bthp", cb * decay, xc, precision=_HI)
             + jnp.exp(cum)[..., None] * jnp.einsum(
                 "bthn,bhpn->bthp", ch, S, precision=_HI))
        outs.append(y)
        tail = jnp.exp(cum[:, -1:] - cum)  # [B, L, H]
        S = (jnp.exp(cum[:, -1])[..., None, None] * S
             + jnp.einsum("bshp,bshn->bhpn", xc * tail[..., None], bh,
                          precision=_HI))
    return jnp.concatenate(outs, axis=1), S


# ---------------------------------------------------------------------------
# the prefill kernel
# ---------------------------------------------------------------------------


def _mm(a, b):
    """A plain product of float32 operands at full precision (six passes of
    the MXU): a bf16 pass is a lower precision than the state's."""
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), precision=_HI,
        preferred_element_type=_F32)


def _chunk_kernel(snap_ref, layer_ref, in_ref, fresh_ref, keep_ref, x_ref,
                  c_ref, bt_ref, cum_ref, cuml_ref, cumt_ref, cumtl_ref, pool_in,
                  y_ref, pool_ref, s_scr, snap_scr, sem, *, L: int, N: int,
                  P: int, hq: int, n_snaps: int):
    """A block of ``L`` positions of one group's heads of one row (module
    docstring): grid ``(rows, groups, T / L)``, positions innermost, the
    group's states in ``s_scr`` ``[hq * N, 128]`` from block to block
    (``hq`` lane groups of ``hp = 128 / P`` heads)."""
    del pool_in  # aliased to pool_ref
    row, grp, t_blk = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    hp, t0 = 128 // P, t_blk * L
    slots = pool_ref.at[layer_ref[0]]

    def mine(slot):
        """The group's heads of a slot: ``[hq * N, 128]``, contiguous."""
        return slots.at[slot, pl.ds(grp * (hq * N), hq * N)]

    def keep(j, src):
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

    # the state the row starts from is on its way while the group's C B^T
    # is computed
    resumes = (t_blk == 0) & (fresh_ref[row] == 0)
    load = pltpu.make_async_copy(mine(in_ref[row]), s_scr, sem)
    pl.when(resumes)(load.start)

    @pl.when((t_blk == 0) & (fresh_ref[row] != 0))
    def _zero():
        s_scr[...] = jnp.zeros_like(s_scr)

    c, bt = c_ref[...], bt_ref[...]  # [L, N], [N, L]
    cb = _mm(c, bt)  # [L(t), L(s)]
    # the cumulative log-decay as an exact part and a small one (``_two_sums``):
    # [L, 128] (a head a lane) and [hg, L] each
    cum, cum_lo, cumt, cumt_lo = (
        r[...] for r in (cum_ref, cuml_ref, cumt_ref, cumtl_ref))
    t_id = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    s_id = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    head_of_lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1) // P
    pos = jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0)

    def lane_group(i):
        """``(x [L, 128], cum of each lane's head [L, 128], what the block's
        own positions give to y [L, 128])`` of lane group ``i``."""
        x = x_ref[:, i * 128:(i + 1) * 128]
        zero = jnp.zeros((L, 128), _F32)
        cumx, cumx_lo, y = zero, zero, zero
        for j in range(hp):
            h = i * hp + j
            # exp(cum_t - cum_s), s <= t: a difference that is never positive,
            # the exact parts' first (what is left of two sums near -5 000)
            diff = ((cum[:, h:h + 1] - cumt[h:h + 1, :])
                    + (cum_lo[:, h:h + 1] - cumt_lo[h:h + 1, :]))
            decay = jnp.where(s_id <= t_id, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
            here = head_of_lane == j
            y = y + _mm(cb * decay, jnp.where(here, x, 0.0))
            cumx = jnp.where(here, cum[:, h:h + 1], cumx)
            cumx_lo = jnp.where(here, cum_lo[:, h:h + 1], cumx_lo)
        return x, (cumx, cumx_lo), y

    parts = [lane_group(i) for i in range(hq)]

    @pl.when(resumes)
    def _arrived():
        load.wait()

    keep_where(lambda p: (t_blk == 0) & (p == 0), s_scr)  # as it started

    def state_of(i):
        return s_scr[i * N:(i + 1) * N, :]

    def since(cumx, at):
        """``exp(cum[at] - cum)`` of a lane group's two-part ``cumx`` from a
        row ``at`` = (exact part, small part) [1, 128] of it: <= 1."""
        return jnp.exp(jnp.minimum(
            (at[0] - cumx[0]) + (at[1] - cumx[1]), 0.0))

    for i, (x, cumx, y) in enumerate(parts):
        y_ref[:, i * 128:(i + 1) * 128] = (
            y + jnp.exp(cumx[0] + cumx[1]) * _mm(c, state_of(i)))

    # a state asked for INSIDE the block: the block's sum under a mask
    for j in range(n_snaps):
        @pl.when(wanted(j, lambda p: (p > t0) & (p < t0 + L)))
        def _inside(j=j):
            p = snap_ref[row * n_snaps + j] - t0
            for i, (x, cumx, _) in enumerate(parts):
                upto = tuple(jnp.sum(jnp.where(pos == p - 1, part, 0.0), axis=0,
                                     keepdims=True) for part in cumx)
                w = jnp.where(pos < p, since(cumx, upto), 0.0)
                snap_scr[i * N:(i + 1) * N, :] = (
                    jnp.exp(upto[0] + upto[1]) * state_of(i) + _mm(bt, x * w))
            keep(j, snap_scr)

    for i, (x, cumx, _) in enumerate(parts):
        total = tuple(part[L - 1:L, :] for part in cumx)
        s_scr[i * N:(i + 1) * N, :] = (
            jnp.exp(total[0] + total[1]) * state_of(i)
            + _mm(bt, x * since(cumx, total)))
    keep_where(lambda p: p == t0 + L, s_scr)


# Rows a call of ``radixmesh_ssd_chunk``: ``ops/kda.py::_ROWS_A_CALL``'s
# reason (a call's outputs are kept in VMEM beside the attention kernel's).
_ROWS_A_CALL = 16


def _two_sums(g: jnp.ndarray) -> tuple:
    """The cumulative sum of the log-decay ``g`` [..., L, H] along ``L`` as
    an EXACT part and a small one. A decay between two positions is ``exp``
    of a difference of two such sums; after a run of ``Delta A`` = -40 a
    token both are near -5 000, where a float32 steps by 5e-4 — the decays
    of the positions BEHIND the run would be that wrong (1e-4 of the outputs
    on the chip). ``g`` in multiples of 1/64 sums exactly in float32 (|g|
    held to 1 024: ``exp`` of less is 0 either way), and what is left of
    each ``g`` is under 1/128: differences of the first part are exact, of
    the second near 1e-7."""
    g = jnp.maximum(g, -1024.0)
    hi = jnp.round(g * 64.0) / 64.0
    return jnp.cumsum(hi, axis=-2), jnp.cumsum(g - hi, axis=-2)


def _block(T: int) -> int:
    L = min(T, CHUNK)
    if T % L or L & (L - 1):
        raise ValueError(f"positions {T} must be a multiple of {CHUNK}, or a "
                         f"power of two below it")
    return L


def _chunk_call(x, c, bt, cum, cum_lo, cumt, cumt_lo, pool, layer, state_in,
                fresh, snap_at, state_out, P: int, interpret: bool):
    """``radixmesh_ssd_chunk`` over the rows given: ``x`` [B, T, H * P], ``c``
    [B, G, T, N], ``bt`` [B, G, N, T], ``cum`` / ``cum_lo`` [B, T, G * 128],
    ``cumt`` / ``cumt_lo`` [B, G, hg, T] -> ``(y [B, T, H * P], pool)``."""
    B, G, T, N = c.shape
    hg, K = cumt.shape[2], snap_at.shape[1]
    hq, L = hg * P // 128, _block(T)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, G, T // L),
        in_specs=[
            pl.BlockSpec((None, L, hg * P), lambda r, g, t, *_: (r, t, g)),
            pl.BlockSpec((None, None, L, N), lambda r, g, t, *_: (r, g, t, 0)),
            pl.BlockSpec((None, None, N, L), lambda r, g, t, *_: (r, g, 0, t)),
            pl.BlockSpec((None, L, 128), lambda r, g, t, *_: (r, t, g)),
            pl.BlockSpec((None, L, 128), lambda r, g, t, *_: (r, t, g)),
            pl.BlockSpec((None, None, hg, L), lambda r, g, t, *_: (r, g, 0, t)),
            pl.BlockSpec((None, None, hg, L), lambda r, g, t, *_: (r, g, 0, t)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((None, L, hg * P), lambda r, g, t, *_: (r, t, g)),
            pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[pltpu.VMEM((hq * N, 128), _F32),
                        pltpu.VMEM((hq * N, 128), _F32),
                        pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        functools.partial(_chunk_kernel, L=L, N=N, P=P, hq=hq, n_snaps=K),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands: snap_at, layer, state_in, fresh, state_out, x, c, bt,
        # cum, cum_lo, cumt, cumt_lo, pool
        input_output_aliases={12: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            has_side_effects=True),
        interpret=interpret,
        name="radixmesh_ssd_chunk",
    )(snap_at.reshape(-1), layer, state_in, fresh, state_out.reshape(-1),
      x, c, bt, cum, cum_lo, cumt, cumt_lo, pool)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(4,))
def ssd_chunk_kernel(x, g, Bm, Cm, pool, layer, state_in, fresh, snap_at,
                     state_out, interpret: bool = False):
    """A chunk through the rows' state slots as the Pallas kernel
    ``radixmesh_ssd_chunk`` (module docstring): row ``b`` starts from
    ``pool[layer, state_in[b]]``, or from zeros where ``fresh[b]``, and its
    state after ``snap_at[b, j]`` positions is left in ``pool[layer,
    state_out[b, j]]`` (slot 0: not kept), in place (the slot array is
    donated and aliased). Returns ``(y [B, T, H, P] float32, pool)``. A slot
    one row keeps to must not be the one another row of the launch starts
    from."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    L, hg = _block(T), H // G
    if 128 % P or (hg * P) % 128:
        raise ValueError(
            f"a group's {hg} heads of {P} must fill whole 128-lane rows")
    # what XLA lays for the kernel: the in-block cumulative log-decay (two
    # parts: ``_two_sums``), a head a lane of its group's 128 and a head a
    # row; B transposed; C by group
    def by_lane(cum):
        return jnp.pad(cum, ((0, 0), (0, 0), (0, 0), (0, 128 - hg))).reshape(
            B, T, G * 128)

    cum, cum_lo = (t.reshape(B, T, G, hg) for t in _two_sums(
        g.astype(_F32).reshape(B, T // L, L, H)))
    seqs = (
        x.astype(_F32).reshape(B, T, H * P),
        Cm.astype(_F32).swapaxes(1, 2),
        Bm.astype(_F32).transpose(0, 2, 3, 1),
        by_lane(cum), by_lane(cum_lo),
        cum.transpose(0, 2, 3, 1), cum_lo.transpose(0, 2, 3, 1),
    )
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    rows = (state_in.astype(jnp.int32), fresh.astype(jnp.int32),
            snap_at.astype(jnp.int32), state_out.astype(jnp.int32))
    outs = []
    for r0 in range(0, B, _ROWS_A_CALL):
        sl = slice(r0, r0 + _ROWS_A_CALL)
        y, pool = _chunk_call(*(t[sl] for t in seqs), pool, layer,
                              *(t[sl] for t in rows), P, interpret)
        outs.append(y)
    y = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    return y.reshape(B, T, H, P), pool


def _chunk_ref(x, g, Bm, Cm, pool, layer, state_in, fresh, snap_at, state_out):
    """:func:`ssd_chunk_kernel` as a gather, :func:`ssd_recurrent_ref` and a
    scatter (every state, wanted or not: slot 0 is nobody's)."""
    H, P = x.shape[2:]
    S0 = jnp.where(fresh[:, None, None] != 0, 0.0, pool[layer, state_in])
    y, snaps = ssd_recurrent_ref(x, g, Bm, Cm, unpack_state(S0, H, P), snap_at)
    snaps = pack_state(snaps)
    return y, pool.at[layer, state_out.reshape(-1)].set(
        snaps.reshape(-1, *snaps.shape[2:]))


def ssd_chunk(x, g, Bm, Cm, pool, layer, state_in, fresh, snap_at, state_out,
              use_kernel: bool | None = None, interpret: bool = False):
    """A chunk through the rows' state slots (``pool`` [layers, slots, H /
    hp * N, 128] float32) -> ``(y [B, T, H, P] float32, pool)`` by lowering
    target (``ops/attention.py::_dispatch``'s rule): the kernel where the
    computation is lowered for a TPU, the ``lax.scan`` anywhere else; an
    explicit ``use_kernel`` is honoured."""
    args = (x, g, Bm, Cm, pool, jnp.asarray(layer, jnp.int32),
            state_in.astype(jnp.int32), fresh.astype(jnp.int32), snap_at,
            state_out.astype(jnp.int32))
    if use_kernel is None:
        return jax.lax.platform_dependent(
            *args, tpu=ssd_chunk_kernel, default=_chunk_ref)
    if use_kernel:
        return ssd_chunk_kernel(*args, interpret=interpret)
    return _chunk_ref(*args)


# ---------------------------------------------------------------------------
# one position a row, state slots in place
# ---------------------------------------------------------------------------


def _decode_kernel(layer_ref, slot_ref, cols_ref, x_ref, a_ref, s_ref, y_ref,
                   out_ref, *, N: int, G: int, hq: int):
    del layer_ref, slot_ref  # the index maps read them
    for grp in range(G):
        b_col = jnp.broadcast_to(cols_ref[:, grp:grp + 1], (N, 128))
        c_col = jnp.broadcast_to(cols_ref[:, G + grp:G + grp + 1], (N, 128))
        for i in range(hq):
            q = grp * hq + i
            rows = slice(q * N, (q + 1) * N)
            S = s_ref[rows, :] * a_ref[q:q + 1, :] + b_col * x_ref[q:q + 1, :]
            out_ref[rows, :] = S
            y_ref[q:q + 1, :] = jnp.sum(S * c_col, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(4,))
def ssd_decode_kernel(x, g, Bm, Cm, pool, layer, slots, interpret: bool = False):
    """One position a row as the Pallas kernel ``radixmesh_ssd_decode``:
    ``x`` [B, H, P], ``g`` [B, H], ``Bm``, ``Cm`` [B, G, N]; ``pool`` [layers,
    slots, H / hp * N, 128] float32 (donated, aliased); row ``b``'s state is
    read from ``pool[layer, slots[b]]`` and the new one written back there.
    Returns ``(y [B, H, P] float32, pool)``. Rows that name the same slot
    (idle rows: slot 0) leave any of their states there."""
    B, H, P = x.shape
    G, N = Bm.shape[1:]
    Q = H * P // 128
    # B and C of every group as COLUMNS (a vector down the sublanes times
    # the state needs no relayout in the kernel); x and the decay as rows
    cols = jnp.concatenate([Bm, Cm], axis=1).astype(_F32).swapaxes(1, 2)
    a = jnp.repeat(jnp.exp(g.astype(_F32)), P, axis=1).reshape(B, Q, 128)
    row = pl.BlockSpec((None, Q, 128), lambda b, *_: (b, 0, 0))
    state = pl.BlockSpec((None, None, Q * N, 128),
                         lambda b, layer, slot: (layer[0], slot[b], 0, 0))
    y, pool = pl.pallas_call(
        functools.partial(_decode_kernel, N=N, G=G, hq=Q // G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((None, N, 2 * G), lambda b, *_: (b, 0, 0)),
                      row, row, state],
            out_specs=[row, state],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, Q, 128), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands: layer, slots, cols, x, a, pool -> pool is input 5, output 1
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="radixmesh_ssd_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      cols, x.astype(_F32).reshape(B, Q, 128), a, pool)
    return y.reshape(B, H, P), pool


def ssd_decode_ref(x, g, Bm, Cm, pool, layer, slots):
    """:func:`ssd_decode_kernel` in ``jax.numpy``: a gather of the rows'
    slots, the update, a scatter back."""
    H, P = x.shape[1:]
    x, g, Bm, Cm = (t.astype(_F32) for t in (x, g, Bm, Cm))
    y, S = _step(unpack_state(pool[layer, slots], H, P), x, jnp.exp(g),
                 _of_heads(Bm, H), _of_heads(Cm, H))
    return y, pool.at[layer, slots].set(pack_state(S))


def ssd_decode(x, g, Bm, Cm, pool, layer, slots,
               use_kernel: bool | None = None, interpret: bool = False):
    """One position a row through the state slots, by lowering target."""
    args = (x, g, Bm, Cm, pool, jnp.asarray(layer, jnp.int32), slots)
    if use_kernel is None:
        return jax.lax.platform_dependent(
            *args, tpu=ssd_decode_kernel, default=ssd_decode_ref)
    if use_kernel:
        return ssd_decode_kernel(*args, interpret=interpret)
    return ssd_decode_ref(*args)
