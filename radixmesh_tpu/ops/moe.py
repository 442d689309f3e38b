"""Sparse expert layer: route over all experts, sort the (token, expert)
pairs by expert, one grouped matrix product per projection, weighted
combine.

    r = W_r x (float32)      p = softmax(r) over all E experts
    S = the k largest of p   w_e = p_e / sum_S p   (``norm_topk``)
    y = sum_{e in S} w_e * W_down,e (silu(W_gate,e x) * W_up,e x)

or, with a sigmoid router: ``p = sigmoid(r)``, ``S`` = the k largest of
``p + b`` (a learned bias), ``w_e = p_e / sum_S p`` times a constant. An
UN-GATED expert (``w_gate`` None) is ``W_down,e relu(W_up,e x)^2``: two
grouped products where SwiGLU has three.

No token is dropped and there is no capacity factor: every pair is
computed. The pairs are laid out expert by expert, each expert's rows
padded up to a whole tile of ``tm`` rows, so that a tile of the grouped
product belongs to exactly one expert (``expert_layout``). The product is
one Pallas kernel, named by its call site for the device trace
(``radixmesh_moe_gmm_decode`` / ``radixmesh_moe_gmm_prefill``): grid over
the row tiles that are in use, the expert's whole ``[K, N]`` weight as one
block. Consecutive tiles of one expert keep the same block index, so the
pipeline fetches an expert's weight once however many rows it was given,
and an expert that no row chose is never fetched: a decode step streams
the experts that were hit and nothing else.

An expert share (``moe_ffn(first_held=)``): the weights hold ``E_held`` of
the router's ``E`` experts. The layer routes over all ``E``; pairs whose
expert is held elsewhere sort behind the held ones into tiles that are
never run and add nothing to ``y``; nothing stands in for the other
shares, and nothing is exchanged.

A ``jax.numpy`` path of the same layer serves every other lowering target
(the CPU): a scan over the experts, each applied to all tokens and
weighted by the routing weight, which is zero where the expert was not
chosen.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["route", "expert_layout", "grouped_matmul", "moe_ffn", "STATS",
           "SHARE_STATS", "GROUP_STATS"]

# What one expert-layer launch adds to the ``moe`` counters, in order.
# ``assignments``, ``experts_hit`` and ``max_load`` count the experts this
# chip HOLDS (all of them without a share). Under an expert share a fifth
# counts the (token, expert) pairs the router gave to experts of other
# shares.
STATS = ("layer_launches", "assignments", "experts_hit", "max_load")
SHARE_STATS = STATS + ("assignments_elsewhere",)
# A router with groups (``n_group`` > 1) reports all five and a sixth: the
# (token, kept group) pairs whose group has experts held here — under a
# share that is one routing group, the tokens that reach this chip at all.
GROUP_STATS = SHARE_STATS + ("groups_kept",)

_PREC = jax.lax.Precision.HIGHEST
_VMEM_LIMIT = 64 * 1024 * 1024


def _route(x, w_router, k, norm_topk, scoring, bias, scale, n_group, topk_group):
    """:func:`route`, and the groups it kept ``[T, n_group]`` bool (None
    for a router without groups)."""
    r = jnp.einsum(
        "th,he->te", x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=_PREC,
    )
    kept = None
    if scoring == "softmax":
        top_p, top_e = jax.lax.top_k(jax.nn.softmax(r, axis=-1), k)
    else:
        p = jax.nn.sigmoid(r)
        choice = p if bias is None else p + bias
        if n_group > 1:
            T, E = choice.shape
            grouped = choice.reshape(T, n_group, E // n_group)
            best_two = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
            _, groups = jax.lax.top_k(best_two, topk_group)  # [T, topk_group]
            kept = jnp.any(
                groups[..., None] == jnp.arange(n_group, dtype=groups.dtype),
                axis=1)
            choice = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(T, E)
        _, top_e = jax.lax.top_k(choice, k)
        top_p = jnp.take_along_axis(p, top_e, axis=-1)
    if norm_topk:
        total = jnp.sum(top_p, axis=-1, keepdims=True)
        top_p = top_p / (total if scoring == "softmax" else total + 1e-20)
    if scale != 1.0:
        top_p = top_p * scale
    return top_e.astype(jnp.int32), top_p, kept


def route(x: jnp.ndarray, w_router: jnp.ndarray, k: int, norm_topk: bool,
          scoring: str = "softmax", bias: jnp.ndarray | None = None,
          scale: float = 1.0, n_group: int = 1, topk_group: int = 1):
    """``x`` [T, H] -> (expert ids [T, k] int32, weights [T, k] float32).
    All arithmetic in float32, ties to the lower expert id
    (``lax.top_k``). ``scoring`` "softmax": softmax over ALL experts
    before the top-k (the source's order). "sigmoid": each expert's own
    sigmoid score; the k are chosen by score + ``bias`` [E] and weighed by
    the score alone. ``norm_topk`` divides the chosen weights by their sum
    (+ 1e-20 under sigmoid, as the source does); ``scale`` multiplies them.
    ``n_group`` > 1 (a sigmoid router): the experts are ``n_group`` runs of
    ``E / n_group``; a group's score is the sum of its two largest score +
    bias, the ``topk_group`` best groups stay (ties to the lower group) and
    the k are chosen among their experts alone. ``n_group`` 1 traces what
    a router without groups traces."""
    return _route(x, w_router, k, norm_topk, scoring, bias, scale, n_group,
                  topk_group)[:2]


def tile_rows(n_tokens: int, k: int, n_experts: int) -> int:
    """Rows per tile of the grouped product: about the mean load of an
    expert, a power of two within [16, 256] (16 rows fill a bf16 sublane
    tile; past 256 the padding of each expert outgrows the saving)."""
    mean = max(1, -(-n_tokens * k // n_experts))
    return min(256, max(16, 1 << (mean - 1).bit_length()))


def expert_layout(top_e: jnp.ndarray, n_experts: int, tm: int,
                  n_live: int | None = None) -> dict:
    """The pairs of ``top_e`` [T, k] sorted by expert (a counting sort,
    stable in pair order), each expert's run padded to a multiple of
    ``tm``. Returns ``row_token`` [M_pad] (the token of each row; pad rows
    name token 0 and are never gathered back), ``dest`` [T, k] (the row of
    each pair), ``tile_group`` [M_pad // tm] (the expert of each tile)
    and ``num_tiles`` (tiles in use). With ``n_live`` < ``n_experts`` the
    groups from ``n_live`` on sort last and their tiles are not in use:
    pairs that belong to no expert held here (an expert share)."""
    n_live = n_experts if n_live is None else n_live
    T, k = top_e.shape
    M = T * k
    m_pad = -(-(M + n_experts * (tm - 1)) // tm) * tm
    flat = top_e.reshape(M)
    onehot = flat[:, None] == jnp.arange(n_experts, dtype=jnp.int32)[None, :]
    ranks = jnp.cumsum(onehot.astype(jnp.int32), axis=0)  # inclusive
    pos = jnp.take_along_axis(ranks, flat[:, None], axis=1)[:, 0] - 1
    sizes = ranks[-1]
    padded = (sizes + tm - 1) // tm * tm
    ends = jnp.cumsum(padded)
    dest = (ends - padded)[flat] + pos
    row_token = jnp.zeros((m_pad,), jnp.int32).at[dest].set(
        jnp.arange(M, dtype=jnp.int32) // k
    )
    tile_group = jnp.minimum(
        jnp.searchsorted(
            ends // tm, jnp.arange(m_pad // tm, dtype=jnp.int32), side="right"
        ),
        n_live - 1,
    ).astype(jnp.int32)
    return {
        "row_token": row_token,
        "dest": dest.reshape(T, k),
        "tile_group": tile_group,
        "num_tiles": (ends[n_live - 1] // tm).astype(jnp.int32),
    }


def _column_blocks(tm: int, K: int, N: int, itemsize: int,
                   budget: int = 12 * 1024 * 1024) -> int:
    """The fewest equal column blocks of ``N`` (each whole 128-lane tiles)
    whose double-buffered weight, row tile and output fit ``budget``."""
    lanes = N // 128 if N % 128 == 0 else 1
    for n in range(1, lanes + 1):
        nb = N // n
        if lanes % n == 0 and 2 * (K * nb * itemsize + tm * K * itemsize
                                   + tm * nb * 4) <= budget:
            return n
    return lanes


def _gmm_kernel(tile_group_ref, layer_ref, lhs_ref, rhs_ref, out_ref, *,
                precision):
    del tile_group_ref, layer_ref  # consumed by the index maps
    out_ref[...] = jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision,
    )


def grouped_matmul(
    lhs: jnp.ndarray,  # [M_pad, K] rows sorted by expert, tile-aligned
    rhs: jnp.ndarray,  # [L, E, K, N]: every layer's experts, stacked
    layer: jnp.ndarray,  # () which layer's
    tile_group: jnp.ndarray,  # [M_pad // tm] expert of each row tile
    num_tiles: jnp.ndarray,  # () tiles in use
    *,
    tm: int,
    site: str,  # "decode" | "prefill": the kernel's name in the trace
    interpret: bool = False,
    fit_vmem: bool = False,  # ask for no VMEM: column blocks that fit
) -> jnp.ndarray:
    """``out[tile] = lhs[tile] @ rhs[layer, tile_group[tile]]`` for the
    tiles in use, float32 out. Rows of tiles past ``num_tiles`` are not
    written. The kernel takes the STACKED weights and the layer's index
    (scalar prefetch, as the attention kernels take the whole pool): a
    layer's slice handed to a custom call is a copy, 0.8 GB a layer at
    the published widths — three times the step (PERF.md, PR 27).

    ``fit_vmem``: the kernel asks for NO VMEM beyond the compiler's default
    (16 MiB): an expert's weight goes through in the fewest column blocks
    whose double buffers fit (:func:`_column_blocks`), columns OUTERMOST, so
    an expert's block is still fetched once however many row tiles it has
    (a row tile is fetched once a column block instead). For a program whose
    other kernels' operands the compiler keeps in VMEM: a kernel that asks
    for 64 MiB there is told it owns VMEM from offset 0 and never returned
    (PERF.md, PR 53; ``vmem_limit_bytes`` wants a compiler flag beside it)."""
    m_pad, K = lhs.shape
    N = rhs.shape[-1]
    precision = _PREC if lhs.dtype == jnp.float32 else None
    if fit_vmem:
        nb = N // _column_blocks(tm, K, N, lhs.dtype.itemsize)
        grid = (N // nb, num_tiles)
        in_specs = [
            pl.BlockSpec((tm, K), lambda j, i, tg, ly: (i, 0)),
            pl.BlockSpec((None, None, K, nb),
                         lambda j, i, tg, ly: (ly[0], tg[i], 0, j)),
        ]
        out_specs = pl.BlockSpec((tm, nb), lambda j, i, tg, ly: (i, j))
        params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"))
    else:
        grid = (num_tiles,)
        in_specs = [
            pl.BlockSpec((tm, K), lambda i, tg, ly: (i, 0)),
            pl.BlockSpec((None, None, K, N),
                         lambda i, tg, ly: (ly[0], tg[i], 0, 0)),
        ]
        out_specs = pl.BlockSpec((tm, N), lambda i, tg, ly: (i, 0))
        params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT,
        )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, precision=precision),
        out_shape=jax.ShapeDtypeStruct((m_pad, N), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
            out_specs=out_specs,
        ),
        compiler_params=params,
        interpret=interpret,
        name=f"radixmesh_moe_gmm_{site}",
    )(tile_group, jnp.asarray(layer, jnp.int32).reshape(1), lhs, rhs)


def _act(gate, up):
    """SwiGLU, or (no gate) the un-gated ``relu(up)^2``, in float32."""
    if gate is None:
        return jnp.square(jax.nn.relu(up))
    return jax.nn.silu(gate) * up


def _without_gate(fn, x, top_e, top_w, *rest):
    return fn(x, top_e, top_w, None, *rest)


def _experts_kernel(x, top_e, top_w, w_gate, w_up, w_down, layer, *, site,
                    interpret, n_routed, fit_vmem=False):
    T, k = top_e.shape
    E = w_up.shape[1]  # experts held here; a pair of another share is E
    tm = tile_rows(T, k, n_routed)
    with jax.named_scope("rm_moe_route"):
        lay = expert_layout(top_e, E + (E < n_routed), tm, n_live=E)
        rows = x[lay["row_token"]]  # [M_pad, H]
    gmm = functools.partial(
        grouped_matmul, layer=layer, tile_group=lay["tile_group"],
        num_tiles=lay["num_tiles"], tm=tm, site=site, interpret=interpret,
        fit_vmem=fit_vmem,
    )
    gate = None if w_gate is None else gmm(rows, w_gate)
    h = _act(gate, gmm(rows, w_up)).astype(x.dtype)
    out = gmm(h, w_down)  # [M_pad, H] float32
    with jax.named_scope("rm_moe_combine"):
        # A pair of another share lies in a tile that was not computed.
        picked = jnp.where((top_e < E)[..., None], out[lay["dest"]], 0.0)
        return jnp.sum(picked * top_w[..., None], axis=1)  # [T, H]


def _experts_reference(x, top_e, top_w, w_gate, w_up, w_down, layer):
    """The same layer without the sort: every expert applied to all
    tokens, weighted by its routing weight (zero where it was not chosen),
    one expert at a time."""
    gated = w_gate is not None
    w_gate, w_up, w_down = (
        jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
        for w in (w_gate if gated else w_up, w_up, w_down)
    )
    E = w_up.shape[0]
    hit = top_e[..., None] == jnp.arange(E, dtype=jnp.int32)  # [T, k, E]
    w_full = jnp.sum(jnp.where(hit, top_w[..., None], 0.0), axis=1)  # [T, E]

    def one(acc, xs):
        wg, wu, wd, w_e = xs
        gate = jnp.einsum("th,hf->tf", x, wg, precision=_PREC,
                          preferred_element_type=jnp.float32) if gated else None
        up = jnp.einsum("th,hf->tf", x, wu, precision=_PREC,
                        preferred_element_type=jnp.float32)
        h = _act(gate, up).astype(x.dtype)
        o = jnp.einsum("tf,fh->th", h, wd, precision=_PREC,
                       preferred_element_type=jnp.float32)
        return acc + w_e[:, None] * o, None

    acc0 = jnp.zeros(x.shape, jnp.float32)
    y, _ = jax.lax.scan(one, acc0, (w_gate, w_up, w_down, w_full.T))
    return y


def moe_ffn(
    x: jnp.ndarray,  # [T, H]
    w_router: jnp.ndarray,  # [H, E]
    w_gate: jnp.ndarray | None,  # [E, H, F] (every layer's: [L, E, H, F]);
    w_up: jnp.ndarray,  # [E, H, F]      None: un-gated experts, relu(up)^2
    w_down: jnp.ndarray,  # [E, F, H]
    *,
    layer: jnp.ndarray | int = 0,  # which layer of stacked expert weights
    k: int,
    norm_topk: bool,
    site: str,
    scoring: str = "softmax",  # the router (``route``)
    bias: jnp.ndarray | None = None,  # [E] selection bias of a sigmoid router
    scale: float = 1.0,  # multiplies the chosen weights
    valid: jnp.ndarray | None = None,  # [T] rows that are real tokens
    first_held: int = 0,  # the first expert this chip holds (an expert share)
    n_group: int = 1,  # group-limited routing (``route``)
    topk_group: int = 1,
    use_kernel: bool | None = None,
    interpret: bool = False,
    fit_vmem: bool = False,  # the grouped product's (``grouped_matmul``)
):
    """The expert layer on ``x``. Returns ``(y [T, H] in x's dtype, expert
    ids [T, k], stats [4] int32)``; ``stats`` is what this launch adds to
    the counters named in ``STATS`` (``SHARE_STATS``, [5], under an expert
    share; ``GROUP_STATS``, [6], for a router with groups), over the rows ``valid`` marks (all, if None). A layer scan
    passes the stacked expert weights and its ``layer`` index, never a
    slice (``grouped_matmul``). ``use_kernel=None`` follows the lowering
    target: the Pallas kernel for a TPU, the ``jax.numpy`` path for
    anything else.

    An expert share: the router is as wide as the model's experts
    (``w_router`` [H, E]) while the weights hold ``E_held`` of them,
    experts ``[first_held, first_held + E_held)``. The layer routes over
    all ``E``, computes the part of the result its own experts give (the
    weights normalised over all ``k`` chosen, as published) and counts
    what went elsewhere; nothing stands in for the other shares."""
    if w_up.ndim == 3:
        w_gate = None if w_gate is None else w_gate[None]
        w_up, w_down = w_up[None], w_down[None]
    layer = jnp.asarray(layer, jnp.int32)
    E, held = w_router.shape[1], w_up.shape[1]
    with jax.named_scope("rm_moe_route"):
        top_e, top_w, kept = _route(
            x, w_router, k, norm_topk, scoring, bias, scale, n_group, topk_group)
        ok = jnp.ones(x.shape[:1], bool) if valid is None else valid
        local = top_e - first_held
        local = jnp.where((local >= 0) & (local < held), local, held)
        hit = (local[..., None] == jnp.arange(held, dtype=jnp.int32)) & ok[
            :, None, None
        ]
        load = jnp.sum(hit, axis=(0, 1)).astype(jnp.int32)  # [held]
        here = jnp.sum(load)
        stats = [jnp.int32(1), here, jnp.sum(load > 0).astype(jnp.int32),
                 jnp.max(load)]
        if held < E or kept is not None:
            # a share: what the router gave to the other shares
            stats.append(jnp.sum(ok).astype(jnp.int32) * k - here)
        if kept is not None:
            size = E // n_group
            mine = slice(first_held // size, -(-(first_held + held) // size))
            stats.append(jnp.sum(kept[:, mine] & ok[:, None]).astype(jnp.int32))
        stats = jnp.stack(stats)

    def kernel(*args):
        return _experts_kernel(*args, site=site, interpret=interpret,
                               n_routed=E, fit_vmem=fit_vmem)

    reference = _experts_reference
    args = (x, local, top_w, w_gate, w_up, w_down, layer)
    if w_gate is None:  # (a None among the operands below is no operand)
        kernel, reference = (functools.partial(_without_gate, fn)
                             for fn in (kernel, reference))
        args = (x, local, top_w, w_up, w_down, layer)
    if use_kernel is None:
        y = jax.lax.platform_dependent(*args, tpu=kernel, default=reference)
    else:
        y = (kernel if use_kernel else reference)(*args)
    return y.astype(x.dtype), top_e, stats
