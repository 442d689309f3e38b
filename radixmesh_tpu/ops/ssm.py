"""Selective state-space scan (Mamba-1): ``h_t = exp(dt_t * A) * h_{t-1} +
(dt_t * x_t) * B_t``, ``y_t = sum_n h_t[n] * C_t[n] + D * x_t``, per channel,
``n`` over the ``d_state`` entries of the channel's state. The state is
laid ``[d_state, channels]`` — 16 sublanes by a lane-dense run of channels
— in the kernel, in the state slots (``cache/kv_pool.py``) and here alike,
float32 throughout.

Three forms of one recurrence:

- :func:`ssm_step` — one position a row (a decode step): plain XLA, one
  fused elementwise update.
- :func:`selective_scan_ref` — ``lax.scan`` over positions; the CPU path
  and the oracle of the kernel.
- ``radixmesh_ssm_scan`` (:func:`selective_scan_kernel`) — the prefill
  kernel. A program holds ``h`` for a block of channels in VMEM and walks
  the chunk's positions, so the ``[T, channels, d_state]`` tensor an
  ``associative_scan`` would materialise (320 KiB a token and layer at
  5120 x 16) never exists. What bounds it: the vector unit and ``exp`` —
  every position costs ``d_state x channels`` exponentials and ~6 vector
  operations on them, serial in the position, while its HBM traffic is
  ~12 B a channel and position (x, dt in, y out): its share of the HBM
  roofline is LOW by construction (``ssm_scan_hbm_share``), and a share
  near 100 would mean the walk had become free.

All three start from a row's own state ``h0`` and hand back the state
after ``snap_at[b, k]`` positions of row ``b`` (0: ``h0`` itself) — an
index a row, so a padded row's state is exact at its true end and a
snapshot inside a chunk is an input, never a compiled shape
(``models/llama.py::_short_conv`` keeps the same contract).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssm_step", "selective_scan", "selective_scan_ref",
           "selective_scan_kernel"]


def ssm_step(x, dt, b, c, a, d, h):
    """One position: ``x``, ``dt`` [B, C] float32, ``b``, ``c`` [B, N],
    ``a`` [N, C] (negative), ``d`` [C], ``h`` [B, N, C] -> ``(y [B, C],
    h_new [B, N, C])``."""
    h = jnp.exp(dt[:, None, :] * a[None]) * h + (dt * x)[:, None, :] * b[:, :, None]
    y = jnp.sum(h * c[:, :, None], axis=1) + d[None] * x
    return y, h


def selective_scan_ref(x, dt, b, c, a, d, h0, snap_at):
    """``x``, ``dt`` [B, T, C] float32; ``b``, ``c`` [B, T, N]; ``a`` [N,
    C]; ``d`` [C]; ``h0`` [B, N, C]; ``snap_at`` [B, K] ->
    ``(y [B, T, C] float32, states [B, K, N, C])``."""
    K = snap_at.shape[1]
    snaps0 = jnp.broadcast_to(h0[:, None], (h0.shape[0], K, *h0.shape[1:]))

    def step(carry, xs):
        h, snaps = carry
        xt, dtt, bt, ct, t = xs
        y, h = ssm_step(xt, dtt, bt, ct, a, d, h)
        hit = (snap_at == t + 1)[:, :, None, None]
        return (h, jnp.where(hit, h[:, None], snaps)), y

    T = x.shape[1]
    (_, snaps), ys = jax.lax.scan(
        step, (h0, snaps0),
        (x.swapaxes(0, 1), dt.swapaxes(0, 1), b.swapaxes(0, 1),
         c.swapaxes(0, 1), jnp.arange(T)))
    return ys.swapaxes(0, 1), snaps


def _scan_kernel(snap_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, h0_ref,
                 y_ref, st_ref, h_scr, *, tblk: int, n_snaps: int):
    row, t_blk = pl.program_id(0), pl.program_id(2)

    @pl.when(t_blk == 0)
    def _start():
        h_scr[...] = h0_ref[...]
        for k in range(n_snaps):
            st_ref[k] = h0_ref[...]

    a = a_ref[...]  # [N, Cb]
    d = d_ref[...]  # [1, Cb]

    def step(i, _):
        xt = x_ref[pl.ds(i, 1), :]  # [1, Cb]
        dtt = dt_ref[pl.ds(i, 1), :]
        h = jnp.exp(dtt * a) * h_scr[...] + (dtt * xt) * b_ref[i]  # [N, 1] over lanes
        h_scr[...] = h
        y_ref[pl.ds(i, 1), :] = (
            jnp.sum(h * c_ref[i], axis=0, keepdims=True) + d * xt)
        done = t_blk * tblk + i + 1
        for k in range(n_snaps):
            @pl.when(done == snap_ref[row * n_snaps + k])
            def _keep(k=k):
                st_ref[k] = h
        return 0

    jax.lax.fori_loop(0, tblk, step, 0)


def _channel_block(channels: int) -> int:
    for blk in (1024, 512, 256, 128):
        if channels % blk == 0:
            return blk
    return channels


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_kernel(x, dt, b, c, a, d, h0, snap_at, interpret: bool = False):
    """:func:`selective_scan_ref` as the Pallas kernel ``radixmesh_ssm_scan``
    (module docstring). Grid ``(rows, channel blocks, position blocks)``,
    positions innermost: ``h`` stays in VMEM scratch from block to block,
    the snapshots' block stays resident and is written where a position's
    count meets ``snap_at``."""
    B, T, C = x.shape
    N = a.shape[0]
    K = snap_at.shape[1]
    cb = _channel_block(C)
    tb = min(T, 256)
    if T % tb:
        raise ValueError(f"positions {T} must be a multiple of {tb}")
    f32 = jnp.float32
    seq = pl.BlockSpec((None, tb, cb), lambda r, j, t, *_: (r, t, j))
    col = pl.BlockSpec((None, tb, N, 1), lambda r, j, t, *_: (r, t, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, C // cb, T // tb),
        in_specs=[
            seq, seq, col, col,
            pl.BlockSpec((N, cb), lambda r, j, t, *_: (0, j)),
            pl.BlockSpec((1, cb), lambda r, j, t, *_: (0, j)),
            pl.BlockSpec((None, N, cb), lambda r, j, t, *_: (r, 0, j)),
        ],
        out_specs=[
            seq,
            pl.BlockSpec((None, K, N, cb), lambda r, j, t, *_: (r, 0, 0, j)),
        ],
        scratch_shapes=[pltpu.VMEM((N, cb), f32)],
    )
    y, states = pl.pallas_call(
        functools.partial(_scan_kernel, tblk=tb, n_snaps=K),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, T, C), f32),
                   jax.ShapeDtypeStruct((B, K, N, C), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="radixmesh_ssm_scan",
    )(
        snap_at.astype(jnp.int32).reshape(-1),
        x.astype(f32), dt.astype(f32),
        b.astype(f32)[..., None], c.astype(f32)[..., None],
        a.astype(f32), d.astype(f32).reshape(1, C), h0.astype(f32),
    )
    return y, states


def selective_scan(x, dt, b, c, a, d, h0, snap_at, use_kernel: bool | None = None,
                   interpret: bool = False):
    """The scan by lowering target (``ops/attention.py::_dispatch``'s
    rule): the kernel where the computation is lowered for a TPU, the
    ``lax.scan`` anywhere else; an explicit ``use_kernel`` is honoured."""
    args = (x, dt, b, c, a, d, h0, snap_at)
    if use_kernel is None:
        return jax.lax.platform_dependent(
            *args, tpu=selective_scan_kernel, default=selective_scan_ref)
    if use_kernel:
        return selective_scan_kernel(*args, interpret=interpret)
    return selective_scan_ref(*args)
