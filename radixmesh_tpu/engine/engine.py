"""Continuous-batching serving engine over the radix prefix cache.

Realizes the scheduler contract the reference documents but leaves
commented out (``radix_cache.py:439-519``): prefix match → lock → compute
→ publish (``cache_unfinished_req`` mid-request, ``cache_finished_req`` at
completion) → unlock, with LRU eviction under pool pressure.

TPU-first shape discipline:

- **Prefill** runs per request with sequence/prefix lengths padded to
  power-of-two buckets — O(log max_len²) compiled variants total, each an
  MXU-dense batch-1 call. The cached prefix is gathered right-aligned so
  ragged hit lengths stay exact (``models/llama.py:prefill_forward``).
- **Decode** is ONE fixed-shape jitted step per iteration for the whole
  batch: static ``[max_batch]`` rows, static page-table width. Inactive
  rows point at a reserved scratch page and their outputs are ignored —
  shapes never depend on how many requests are live.
- The KV pool array is donated through both paths; host-side tree
  mutation happens between device steps (SURVEY §7 hard part (c)).

Mesh integration (the reference's core loop, ``radix_mesh.py:193-238``):
pass ``mesh=MeshCache(...)`` and every publish is *also* inserted into the
distributed replica at token granularity, so the ring (and through it the
router) learns which node holds which prefix. Ownership stays split: the
engine's local tree owns slot lifetime (LRU evict → ``pool.free``), the
mesh replica is advertisement-only on a serving node (construct it with
``pool=None`` so distributed GC retires attribution entries without
double-freeing slots the engine still references).
"""

from __future__ import annotations

import bisect
import collections
import functools
import itertools
import time
from dataclasses import dataclass, field
from dataclasses import replace as dataclass_replace
from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from radixmesh_tpu.cache.kv_pool import PagedKVPool
from radixmesh_tpu.cache.radix_tree import RadixTree
from radixmesh_tpu.engine.prefill_plan import (
    KV_BLOCK_PAGES as _KV_BLOCK_PAGES,
    boxed_tokens,
    chunk_width,
    launch_cost,
    plan_prefill,
    pow2_at_least as _pow2_at_least,
    subwaves,
)
from radixmesh_tpu.engine.programs import ProgramStore
from radixmesh_tpu.engine.request import Request, RequestState, SamplingParams
from radixmesh_tpu.models.llama import (
    ModelConfig,
    decode_step,
    new_moe_stats,
    prefill_chunk_paged,
    prefill_forward,
    refuse_unsupported,
)
from radixmesh_tpu.ops.attention import (
    batch_bucket,
    default_use_kernel,
    kernel_shape_ok,
)
from radixmesh_tpu.obs.attribution import shape_bucket
from radixmesh_tpu.obs.fleet_plane import eviction_counters
from radixmesh_tpu.obs.metrics import TOKEN_LEN_BUCKETS, get_registry
from radixmesh_tpu.obs.trace_plane import get_recorder
from radixmesh_tpu.obs.tracing import ProcessAccount, SpanBook, since_import
from radixmesh_tpu.ops.mla_attention import decode_groups
from radixmesh_tpu.ops.sampling import sample_tokens, spec_verify_sample
from radixmesh_tpu.utils.logging import get_logger

# The three big programs of the served path and their static argument
# names (their decorators', models/llama.py): what an engine launches
# through its program store. Keyed by the functions themselves: whatever
# else stands under one of these names at a launch (a test's own jit) is
# called as it is.
_STORED_PROGRAMS = {
    prefill_chunk_paged: ("cfg", "page_size", "kv_block_pages", "mesh",
                          "use_kernel", "interpret"),
    decode_step: ("cfg", "page_size", "mesh"),
    prefill_forward: ("cfg",),
}

# Per-process engine sequence: disaggregated harnesses run a prefill engine
# and a decode engine in one process, so each needs its own metric series.
_engine_seq = itertools.count()

__all__ = ["Engine", "EngineStats"]


_M64 = (1 << 64) - 1


def _mix64(x: int) -> int:
    """splitmix64 finalizer — the same mixing family the tree
    fingerprints use; here it turns (seed, position) into sampling-key
    material."""
    x = (x + 0x9E3779B97F4A7C15) & _M64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _M64
    return x ^ (x >> 31)


# A model with conv layers: the states one row of a prefill launch may
# leave (its working state at the row's end and two snapshots: the prompt's
# page-aligned cut and the tree's match length), and how many of the tree's
# snapshots one pressure on the state slots evicts.
_STATE_SNAPS = 3
_STATE_EVICT_BATCH = 64
# ... and of snapshots that hold a window tail too (each frees up to a
# window of rows: 20 MiB at published widths, not 240 KiB).
_TAIL_EVICT_BATCH = 8
# A pool that runs full (finished prefixes are kept until the room is
# needed) evicts on every allocation, and ``RadixTree.evict`` walks the
# whole tree to find its leaves: 6 ms at 3 000 nodes, on the engine thread,
# for a page. One pressure therefore frees at least this share of the pool.
_EVICT_POOL_SHARE = 64


def pool_groups(cfg: ModelConfig, num_slots: int, max_batch: int,
                page_size: int) -> dict:
    """What ``PagedKVPool`` holds beside the rows for ``cfg``, every size
    derived: the state slots of a model whose layers carry a state (and
    the conv states beside a float32 recurrent part), the window group of
    one whose window layers keep rows of their own."""
    n_states = cfg.default_state_slots(num_slots, max_batch)
    return dict(
        state_shape=cfg.state_shape(n_states),
        state_dtype=cfg.state_dtype,
        conv_state_shape=cfg.conv_state_shape(n_states),
        window_shape=cfg.window_pool_shape(
            cfg.default_window_slots(max_batch, page_size)),
    )


# Upper edges (ms) of ``Engine.admission_wait``'s buckets; one overflow
# bucket follows the last.
_ADMISSION_WAIT_LE_MS = (1, 2, 5, 10, 20, 50, 100, 200, 500, 1000, 2000, 5000)
_ADMISSION_WAIT_LE_S = tuple(ms / 1e3 for ms in _ADMISSION_WAIT_LE_MS)
# A step whose seconds, less what its own prefill waves were expected to
# take, pass this is recorded in ``Engine.slow_steps`` (a decode launch with
# its hand-over is 25-35 ms, a full collection 0.3 s at most); the ring
# keeps the last ``_SLOW_STEP_RING`` records.
_SLOW_STEP_S = 1.0
_SLOW_STEP_RING = 32
# Where a flush of the deferred emission ran (keys of ``Engine.emit``):
# behind a dispatched decode launch, behind a dispatched prefill, or with
# no launch to hide under (end of a step that leaves no row, cancel,
# preemption).
_UNDER_DECODE = "flushes_under_decode"
_UNDER_PREFILL = "flushes_under_prefill"
_EXPOSED = "flushes_exposed"
# Why a decode step's advance ran with the device waiting (keys of
# ``Engine.handover["settled_for"]``): a row of the step sampled a stop id,
# or every row reached its length cap (rows that end by length beside rows
# that go on do not settle: ``Engine._settle_why``); a request was about to
# be admitted, cancelled or preempted; the launch, or what follows it, is
# not a plain decode launch of the same rows; the pool had no page for the
# launch built ahead.
_SETTLED_FOR = ("finish", "admit", "cancel", "preempt", "launch_kind", "no_pages")


@dataclass
class _InlineJob:
    """One partially-prefilled request riding the mixed-wave backlog
    (engine/waves.py). Slots and the batch row are acquired UP FRONT by
    the normal admission path (``_acquire_prompt_slots``); only the
    compute advances chunk-by-chunk — ``pos`` is the exact resume offset
    (tokens of the prompt whose KV is already in the pool), the chunk
    interleave invariant the wave tests pin."""

    req: Request
    row: int
    reuse: int
    own: np.ndarray
    token_slots: np.ndarray  # slot per prompt position (prefix + own)
    pos: int  # next un-prefilled prompt offset (starts at reuse)
    total: int  # len(prompt)


# ``decode_groups``' counts for a launch in which no rows were grouped.
_NO_GROUPS = {"groups": 0, "members": 0, "passes": 0, "shared_tokens": 0}


@dataclass
class _DecodeLaunch:
    """The inputs of one decode launch (``Engine._build_launch``), kept
    with what they were built for: ``sig`` = (row, rid, position the
    row's token is written at) of every row of the launch — the live
    rows, less ``ending``."""

    active: list[tuple[int, Request]]
    sig: list[tuple[int, int, int]]
    # Rows left out of a launch built ahead: the launch on the device
    # takes each to its length cap. They sit in ``Engine._rows`` until
    # that launch's advance has run, and hold the scratch values here.
    ending: frozenset[int]
    idx: np.ndarray  # the live rows, as an index
    slots: np.ndarray  # [max_batch] slot each row's K/V lands in
    max_len: int  # longest context before the launch
    window: tuple[int, int]  # attn_window increments (read, skipped)
    # Latent rows the launch has to read, x layers: every row's context,
    # but what a group of rows shares once and not once a member.
    latent_tokens: int
    contexts: np.ndarray  # the live rows' context, this token included
    slots_d: jax.Array
    pt_d: jax.Array
    lengths_d: jax.Array
    positions_d: jax.Array | None  # the C=1 chunk launch's (pp, seeded)
    # The ids to feed, where the step before left them on the device for
    # exactly these rows; None: upload ``Engine._tokens`` at the launch.
    tokens_d: jax.Array | None = None
    # A model with conv layers: each row's working state slot and where
    # the new state goes besides (its boundary slot when this token
    # completes a page, else slot 0), [2, max_batch], on the device.
    state_d: jax.Array | None = None
    # A model with a window group of rows: each row's slot IN that group
    # for this token, and its page table there (logical pages, as
    # ``pt_d``; what left the window reads the group's scratch page).
    w_slots_d: jax.Array | None = None
    w_pt_d: jax.Array | None = None
    # A latent row read by the decode kernel: which rows hold the same
    # pages first (``decode_groups``: its counts, and its lines on the
    # device).
    groups: dict | None = None
    groups_d: jax.Array | None = None
    # Rows whose state this launch also keeps in their boundary slot.
    boundary_copies: int = 0


@dataclass
class EngineStats:
    """Hit-rate + throughput counters (the reference never increments its
    ``hit_count`` and emits no metrics — SURVEY §5 'observability')."""

    prompt_tokens: int = 0
    cached_tokens: int = 0  # reused from the radix cache at prefill
    generated_tokens: int = 0
    prefills: int = 0
    decode_steps: int = 0
    finished: int = 0
    preemptions: int = 0
    spec_proposed: int = 0  # draft tokens offered for verification
    spec_accepted: int = 0  # draft tokens accepted (KV kept, step skipped)
    spec_rejected: int = 0  # draft tokens rejected by verification
    resurrections: int = 0  # resume-mode admissions (crash recovery)
    replayed_tokens: int = 0  # already-delivered tokens re-prefilled
    replayed_cached_tokens: int = 0  # ... of which the cache served
    ttft_s: list[float] = field(default_factory=list)

    @property
    def hit_rate(self) -> float:
        """Prefix-cache hit-rate over prompt tokens — the north-star
        metric (``BASELINE.json``: target ≥70% on ShareGPT)."""
        return self.cached_tokens / self.prompt_tokens if self.prompt_tokens else 0.0

    @property
    def p50_ttft_s(self) -> float:
        return float(np.median(self.ttft_s)) if self.ttft_s else 0.0


class Engine:
    def __init__(
        self,
        cfg: ModelConfig,
        params: dict,
        *,
        num_slots: int = 4096,
        page_size: int = 16,
        max_batch: int = 8,
        max_seq_len: int | None = None,
        rng_seed: int = 0,
        name: str | None = None,
        host_cache_slots: int = 0,
        pool: PagedKVPool | None = None,
        mesh=None,
        prefill_chunk: int = 512,
        prefill_wave_tokens: int = 4096,
        long_prefill_threshold: int = 1024,
        sp_prefill_threshold: int = 4096,
        prefill_inline_budget: int = 0,
        prefill_inline_max_defer: int = 2,
        spec_decode_tokens: int = 0,
        spec_ngram: int = 3,
        spec_adaptive: bool = False,
        token_timeline_capacity: int = 4096,
        token_stall_threshold_s: float = 0.05,
        kv_quant: str | None = None,
        weight_quant: str | None = None,
        device_mesh=None,
        kv_transfer_async: bool = False,
        kv_transfer_chunk_tokens: int = 512,
        kv_transfer_min_restore_tokens: int = 0,
        kv_tier_dir: str | None = None,
        kv_tier_capacity_bytes: int = 1 << 30,
        kv_tier_watermark: float = 0.7,
        kv_tier_min_heat: float = 0.0,
        kv_tier_destage_budget: int = 16,
        kv_tier_destage_interval_s: float = 0.25,
        stream_publish_tokens: int = 0,
        step_accounting: bool = False,
        peak_tflops: float | None = None,
        process_account: ProcessAccount | None = None,
    ):
        if page_size & (page_size - 1):
            raise ValueError("page_size must be a power of two")
        # A model with experts or window layers: whatever assumes the
        # dense layer is refused here, by name (models/llama.py).
        refuse_unsupported(
            cfg, weight_quant=weight_quant,
            kv_quant=kv_quant or (pool is not None and pool.quant),
            device_mesh=device_mesh,
            spec_decode_tokens=spec_decode_tokens,
            prefill_inline_budget=prefill_inline_budget,
            host_cache_slots=host_cache_slots, kv_tier_dir=kv_tier_dir,
            mesh_cache=mesh, stream_publish_tokens=stream_publish_tokens,
        )
        self.cfg = cfg
        # Multi-chip serving (SURVEY §7 stage 7): tp shards heads/ffn/vocab
        # across the device mesh; the SAME scheduler/tree/publish code runs
        # unchanged — only array placement differs. Qwen2-72B cannot serve
        # on one chip by definition; this is its path.
        self.device_mesh = device_mesh
        # Pipeline-parallel serving (parallel/pp_serving.py): a "pp" mesh
        # axis shards the LAYER axis of the unchanged param/pool pytrees;
        # prefill chunks and decode steps route through pp_forward_chunk
        # while every host-side structure stays identical.
        self._pp = (
            device_mesh is not None and device_mesh.shape.get("pp", 1) > 1
        )
        if weight_quant is not None:
            # W8A16 weights (ops/wquant.py): decode streams half the
            # weight bytes and Llama-3-8B fits one 16 GB v5e. Quantize
            # BEFORE sharding so the scale leaves shard with their
            # weights.
            if weight_quant != "int8":
                raise ValueError(f"unknown weight quantization {weight_quant!r}")
            from radixmesh_tpu.ops.wquant import quantize_params

            params = quantize_params(params)
        self.weight_quant = weight_quant
        if device_mesh is not None:
            tp = device_mesh.shape.get("tp", 1)
            if cfg.n_kv_heads % tp or cfg.n_heads % tp:
                raise ValueError(
                    f"n_heads={cfg.n_heads}/n_kv_heads={cfg.n_kv_heads} must "
                    f"divide tp={tp}"
                )
            if self._pp:
                if cfg.n_layers % device_mesh.shape["pp"]:
                    raise ValueError(
                        f"n_layers={cfg.n_layers} is not divisible by "
                        f"pp={device_mesh.shape['pp']}"
                    )
                from radixmesh_tpu.parallel.pp_serving import shard_params_pp

                params = shard_params_pp(params, cfg, device_mesh)
            else:
                from radixmesh_tpu.models.llama import param_logical_axes
                from radixmesh_tpu.parallel.sharding import shard_params

                params = shard_params(
                    params, param_logical_axes(cfg, params), device_mesh
                )
        self.params = params
        self.page_size = page_size
        self.max_batch = max_batch
        self.max_seq_len = max_seq_len or cfg.max_seq_len
        self.max_pages = -(-self.max_seq_len // page_size)
        # Long-context admission (SURVEY §5): prompts of more than
        # ``long_prefill_threshold`` tokens, and every prompt the tree serves
        # part of, prefill in chunks against the paged pool (O(S·chunk)
        # memory) instead of the dense path (O(S²) scores). ``prefill_chunk``
        # is the MINIMUM chunk (and the inline-wave and speculative paths'
        # own): how wide a chunk launch may be is read from the weights
        # (``prefill_width``, below).
        self.prefill_chunk = prefill_chunk
        # Cold-burst fairness (VERDICT round-4 weak #4): a prefill wave
        # wider than the compute-saturating token count only convoys —
        # every member then finalizes its first token when the LAST one
        # does, so an N-request cold burst lands p50 TTFT == p99 == the
        # whole burst's prefill time. Sub-waves are sliced to at most
        # ``prefill_wave_tokens // chunk`` rows, and no launch holds more
        # tokens than that; slices preserve arrival
        # order (FIFO within a size bucket), so with equal jobs TTFT
        # approaches the single-server SPT floor (mean ≈ half the burst).
        self.prefill_wave_tokens = prefill_wave_tokens
        self.long_prefill_threshold = long_prefill_threshold
        # Sequence-parallel prefill (SURVEY §5 serving-side): fresh prompts
        # at least this long prefill sp-sharded over the device mesh —
        # TTFT scales with the sp axis instead of one chip's FLOPs.
        self.sp_prefill_threshold = sp_prefill_threshold
        # Speculative decoding: draft γ tokens (radix-tree continuation
        # first — a replayed conversation's cached generation — then
        # prompt-lookup n-grams), verify all of them in ONE chunked
        # forward (``prefill_chunk_paged``, C=γ+1), and accept per row —
        # greedy rows by longest argmax match, stochastic rows by exact
        # rejection sampling. Decode latency is weight-streaming-bound,
        # so a verified draft turns γ sequential steps into one
        # matmul-dense pass; rejected tail KV is overwritten by later
        # positional writes.
        self.spec_decode_tokens = spec_decode_tokens
        self.spec_ngram = max(2, spec_ngram)
        # Mixed compute waves (engine/waves.py, the Sarathi-Serve
        # schedule): > 0 arms the wave scheduler — while decode rows are
        # running, admission routes new prompts into an inline backlog
        # that advances up to this many prefill tokens PER WAVE on the
        # same fused chunk launch as the decode step, so a long prompt
        # stops convoying interactive streams. 0 (default) keeps the
        # legacy whole-wave alternation every existing test pins.
        self.prefill_inline_budget = max(0, prefill_inline_budget)
        self.prefill_inline_max_defer = max(0, prefill_inline_max_defer)
        # Last decode launch (path, batch, bucket, max_len) and last
        # prefill launch path for /debug/state, and what attention a
        # launch from this process runs: the Pallas kernel or the jnp
        # reference.
        self._last_dispatch: dict | None = None
        self._last_prefill_path: str | None = None  # "sp" | "dense" | "chunk"
        # Always-on accumulators served on /debug/state (a 3 s profiler
        # capture is taken after the measured window and holds ~8
        # admissions: too few for a statistic).
        # spans: count and host seconds of every rm.* span this thread
        # opens (obs/tracing.py::SpanBook; ``tree_host`` is a view of it).
        # process_account: the process's collections and compilations,
        # where an entry point installed one (launch.py serve); a
        # compilation on this thread is then named by the span it fell in.
        self.spans = SpanBook()
        # tree_publish: the tokens of every published key, and those of
        # them ``_publish`` built, copied or compared (the rest lie above
        # the request's lock); also under ``tree_host``.
        self.tree_publish = {"key_tokens": 0, "walked_tokens": 0}
        self.process_account = process_account
        if process_account is not None:
            process_account.book = self.spans
        # The launches of the three big programs: from a stored executable
        # where JAX's persistent cache is configured and there is no device
        # mesh, jax.jit's own otherwise (engine/programs.py).
        self._launch = ProgramStore(
            _STORED_PROGRAMS, account=process_account, mesh=device_mesh)
        # prefill: the admission waves' work (_admit): sub-waves, their
        # members, the device launches that prefilled them, uncached
        # prompt tokens computed, tokens of the launched shapes (the sum of
        # B x C over the launches sent) and what the one box a sub-wave
        # before PR 47 would have launched for the same rows
        # (``boxed_tokens``: 1 - launched / boxed is what the planner
        # saved, launches / waves what it paid), and host seconds from
        # before the path's dispatch to after the first-token sync — no
        # decode launch is in flight then, so a wave's wall time is its
        # device time plus its dispatch and its sync tail.
        # ``launches_below_floor``: launches whose B x C is under
        # ``prefill_cost[0]``, bound by the weights they stream whatever
        # they hold; ``by_chunk``: launches by their chunk C (a dense or sp
        # launch: its one bucket).
        self.prefill = {
            "waves": 0,
            "rows": 0,
            "launches": 0,
            "launches_below_floor": 0,
            "by_chunk": {},
            "tokens_new": 0,
            "tokens_launched": 0,
            "tokens_boxed": 0,
            "wall_s": 0.0,
            "by_path": {"dense": 0, "chunk": 0, "sp": 0},
        }
        # slow_steps: steps whose excess over their own prefill passed
        # _SLOW_STEP_S (count, excess seconds, the last records).
        self.slow_steps = {
            "n": 0,
            "s": 0.0,
            "threshold_s": _SLOW_STEP_S,
            "last": collections.deque(maxlen=_SLOW_STEP_RING),
        }
        # admission_wait: requeue/dispatch/submission (whichever was
        # last) to the instant a batch row is secured, per admission.
        # sampler: launches by the executable _launch_draws chose.
        self.admission_wait = {
            "count": 0,
            "sum_s": 0.0,
            "le_ms": list(_ADMISSION_WAIT_LE_MS),
            "counts": [0] * (len(_ADMISSION_WAIT_LE_MS) + 1),
        }
        self.sampler = {"argmax_launches": 0, "drawing_launches": 0}
        # emit: decode tokens handed to their observers (_flush_emit) and
        # the flushes by what the device was running beneath them.
        self.emit = {
            "tokens": 0,
            _UNDER_DECODE: 0,
            _UNDER_PREFILL: 0,
            _EXPOSED: 0,
        }
        # handover: how the hand-over between two decode launches went
        # (_decode_once). launches_prebuilt / _rebuilt: the launch's inputs
        # were built behind the launch before it / in the gap before this
        # one. advances_deferred / _settled: a step's per-row advance ran
        # behind the next launch / with the device waiting, the latter by
        # what forced it (keys of _settle's ``why``). finishes_deferred:
        # requests that a deferred advance finished — released and
        # published behind a launch they are no row of.
        self.handover = {
            "launches_prebuilt": 0,
            "launches_rebuilt": 0,
            "advances_deferred": 0,
            "advances_settled": 0,
            "finishes_deferred": 0,
            "settled_for": dict.fromkeys(_SETTLED_FOR, 0),
        }
        # The last step's tokens while their advance waits for the next
        # launch (_consume's arguments), and that launch's inputs, built
        # ahead (_build_launch's record); None: nothing pending / built.
        self._pending: tuple | None = None
        self._prebuilt: _DecodeLaunch | None = None
        # Queued for the next flush. Per token, by _advance_token:
        # (request, step stamp, counted as output). Per decode launch:
        # (start, seconds, latency samples of seconds / samples, the step
        # plane's note_wave arguments, [(trace, decode_chunk attributes)]).
        self._emit_rows: list[tuple[Request, float, bool]] = []
        self._emit_steps: list[tuple] = []
        # moe: the expert layer's counters, accumulated ON THE DEVICE by
        # every step (models/llama.py) and read only when /debug/state is
        # asked; None for a dense model. attn_window: context tokens that
        # decode launches did / did not have to read on window layers,
        # summed over rows and sliding layers (host arithmetic on the
        # lengths, in rm.decode.build); where the Pallas decode kernel
        # serves, the tokens of one of its compute blocks beside them. For a latent cache row the same
        # group counts the rows read: by decode launches (context x layers,
        # summed over rows: latent_tokens_attended; of those, the rows a
        # launch did not have to read again because several of its rows
        # hold the same pages, latent_tokens_shared; the rest,
        # latent_tokens_read) and by prefill launches of the cached part
        # (tokens already in pages that a chunk attends x layers).
        # decode_groups: the last decode launch's groups, members, passes.
        self._moe_stats = (new_moe_stats(cfg.ep_size > 1, cfg.n_group > 1)
                           if cfg.n_experts else None)
        # decode_context_tokens: the live rows' context (this token
        # included) summed over decode launches — bytes of K/V a step read,
        # without guessing the mean context; table_pages_max: the widest
        # page table any launch carried (a decode step's bucket, a prefill
        # launch's), in pages of ``pool.page_size``.
        self.attn_window = {"window_tokens_skipped": 0, "window_tokens_read": 0,
                            "decode_context_tokens": 0, "table_pages_max": 0}
        if cfg.latent:
            self.attn_window |= {
                "latent_tokens_read": 0, "latent_prefix_tokens": 0,
                "latent_tokens_attended": 0, "latent_tokens_shared": 0,
                "decode_groups": _NO_GROUPS,
            }
        # decode_groups of the last launch built, with what it was built
        # for (_grouping): a row's first pages are laid once, when it is
        # installed, so the groups change only when a row joins or leaves.
        self._groups_for: tuple | None = None
        if cfg.dsa:
            # Learned sparse attention, by launch kind (host arithmetic on
            # the lengths, beside latent_tokens_read): index keys a full
            # layer scored, rows the layers attended, and rows the context
            # holds (what dense attention would read), each x its layers.
            self.attn_window["sparse"] = {
                site: {"index_keys_scored": 0, "rows_selected": 0, "rows_held": 0}
                for site in ("decode", "prefill")
            }
        self._sliding_layers = (
            cfg.layer_types.count("sliding_attention") if cfg.has_window else 0
        )
        # The expert ids of the last launch ([L, B, S, k], on the device):
        # fetched only for a request that asked for them.
        self._last_expert_ids = None
        self._last_selected = None  # aux["index_positions"] of the last launch
        # What the paged kernels tile is the pool row: a head, two heads of
        # 64 side by side (ModelConfig.pair_kv_heads), or the one latent row
        # (whole 128-lane tiles whatever nope + rope a head has: 192 here,
        # 256 there).
        kernel_width = (cfg.kv_row[2] if cfg.pair_kv_heads or cfg.latent
                        else cfg.head_dim)
        kernels = default_use_kernel(kernel_width)
        self.chunk_attention = "paged" if kernels else "reference"
        # ``path`` of the decode dispatch record: chip_smoke.py proves
        # from it that the kernel served.
        self._decode_path = "paged" if kernels else "dense"
        self.log = get_logger("engine")
        if jax.default_backend() == "tpu" and not kernel_shape_ok(kernel_width):
            self.log.warning(
                "head_dim=%d is not a multiple of 128: decode and chunked-"
                "prefill attention take the jnp REFERENCE path on this TPU "
                "(the Pallas paged kernels cannot tile it; heads of 64 can "
                "be laid two to a pool row: ModelConfig.pair_kv_heads)",
                cfg.head_dim,
            )
        elif cfg.pair_kv_heads:
            self.log.info(
                "head_dim=64: two kv heads share a 128-lane pool row; the "
                "paged kernels attend %d wide heads, no row is padded",
                cfg.kv_row[1],
            )
        # Resolved early: the KV plane (below) and the metric labels
        # (further down) both key their series on it.
        self.name = name or f"engine{next(_engine_seq)}"
        # Distributed replica (cache/mesh_cache.py): publishes advertise
        # this node's prefixes around the ring so the router can send
        # shared-prefix requests back here (radix_mesh.py:193-238).
        self.mesh = mesh
        mesh_page = getattr(mesh, "page", 1) if mesh is not None else 1
        if mesh_page > 1 and page_size % mesh_page:
            # Page-granular replication ships pool page ids; engine
            # publishes are aligned (and contiguous) at ENGINE pages, so
            # the mesh page must divide it.
            raise ValueError(
                f"mesh page_size {mesh_page} must divide engine "
                f"page_size {page_size}"
            )

        # The cache row is the model's (K and V per kv head, or one
        # latent row): the pool is built from it.
        row_planes, row_heads, row_width = cfg.kv_row
        pool_layers = len(cfg.attn_layers)  # the layers that own rows
        if pool is not None:
            expected = dict(
                page_size=page_size,
                num_layers=pool_layers,
                planes=row_planes,
                num_kv_heads=row_heads,
                head_dim=row_width,
                quant=kv_quant,
                index_shape=cfg.kv_index_shape(pool.num_slots),
                state_shape=cfg.state_shape(
                    cfg.default_state_slots(pool.num_slots, max_batch)),
                window_slots=cfg.default_window_slots(max_batch, page_size),
            )
            if kv_quant is None:
                expected["dtype"] = cfg.dtype
            for attr, want in expected.items():
                got = getattr(pool, attr)
                if got != want:
                    raise ValueError(
                        f"external pool {attr}={got!r} incompatible with "
                        f"model/engine {attr}={want!r}"
                    )
            self.pool = pool
        else:
            pool_sharding = None
            if device_mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec

                if self._pp:
                    from radixmesh_tpu.parallel.pp_serving import pp_pool_spec

                    # Each pipeline stage holds only its own layers' KV,
                    # each tp chip its kv-head shard.
                    pool_sharding = NamedSharding(device_mesh, pp_pool_spec())
                else:
                    # [2, L, Hkv, slots, D]: each chip holds its kv-head
                    # shard of every page (kv_pool.py's head-major layout
                    # rationale).
                    pool_sharding = NamedSharding(
                        device_mesh, PartitionSpec(None, None, "tp", None, None)
                    )
            self.pool = PagedKVPool(
                num_slots=num_slots,
                num_layers=pool_layers,
                num_kv_heads=row_heads,
                head_dim=row_width,
                page_size=page_size,
                dtype=cfg.dtype,
                sharding=pool_sharding,
                quant=kv_quant,
                planes=row_planes,
                index_shape=cfg.kv_index_shape(num_slots),
                # Derived, not a knob: what the pool's size and the batch
                # call for (ModelConfig.default_state_slots /
                # default_window_slots).
                **pool_groups(cfg, num_slots, max_batch, page_size),
            )
        if not cfg.latent:
            from radixmesh_tpu.ops.paged_attention import (
                chunk_query_tokens,
                decode_block_tokens,
            )

            # Static: which block geometry the paged kernels' numbers on
            # this node come from (a chip of a tp mesh holds its heads).
            tp = device_mesh.shape.get("tp", 1) if device_mesh is not None else 1
            shape = dict(Hkv=max(1, row_heads // tp), page=page_size,
                         D=row_width, dtype=self.pool.kv.dtype,
                         quantized=kv_quant is not None)
            if kernels:
                self.attn_window["decode_block_tokens"] = decode_block_tokens(
                    max_pages=self.max_pages, **shape)
            # A chunk launch's query blocks (rows x chunk // the chunk
            # kernel's queries a program: one program a layer each), and
            # those whose first query lies past the row's last new token —
            # padding of the launched shape, which the kernel answers with
            # zeros and no work. Host arithmetic where _prefill_group
            # counts, whichever of kernel and reference serves.
            self.attn_window |= {
                "chunk_query_blocks": 0, "chunk_query_blocks_skipped": 0}
            self._chunk_shape = dict(
                group=cfg.n_heads // row_heads, q_dtype=cfg.dtype, **shape)
            self._chunk_query_tokens = functools.partial(
                chunk_query_tokens, **self._chunk_shape)
        if kv_tier_dir is not None and host_cache_slots <= 0:
            raise ValueError(
                "kv_tier_dir requires a host tier (host_cache_slots > 0): "
                "the disk tier demotes from and restores through host RAM"
            )
        self._kv_tier = None
        if host_cache_slots > 0:
            # Hierarchical cache: HBM-evicted prefixes fall back to a
            # host-RAM tier and are restored on hit instead of recomputed
            # (cache/host_cache.py; the reference's HiCache stubs made real).
            from radixmesh_tpu.cache.host_cache import HierarchicalCache, HostKVStore

            host_store = HostKVStore(
                num_slots=host_cache_slots,
                num_layers=cfg.n_layers,
                num_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim,
                page_size=page_size,
                dtype=cfg.dtype,
                quant=self.pool.quant,
            )
            if kv_tier_dir is not None:
                # Durable third tier (cache/kv_tier.py): checksummed
                # fsynced extent files behind the staged executor, so a
                # whole-cell power loss no longer erases the working
                # set (ROADMAP item 3 + cold-cell resurrection).
                from radixmesh_tpu.cache.kv_tier import DiskKVTier

                self._kv_tier = DiskKVTier(
                    kv_tier_dir,
                    capacity_bytes=kv_tier_capacity_bytes,
                    page_size=page_size,
                    name=self.name,
                )
                # Disk I/O is only reachable through the plane worker
                # (lint-pinned): a tier without the plane would be
                # write-only dead weight, so arm it.
                if not kv_transfer_async:
                    kv_transfer_async = True
                    self.log.info(
                        "kv_tier_dir set: arming the async KV-movement "
                        "plane (disk restores/spills are staged-only)"
                    )
            self.tree: RadixTree = HierarchicalCache(
                self.pool, host_store, disk_tier=self._kv_tier
            )
        else:
            self.tree = RadixTree(
                page_size=page_size, on_free=self.pool.free,
                on_free_state=(
                    None if self.pool.state is None else self.pool.free_state),
                on_free_tail=(
                    None if self.pool.window is None else self._tail_freed),
            )
        # state: a model whose layers carry a state beside the rows
        # (None otherwise). kv_matched_tokens: what the tree's rows alone
        # would have served; resumed_tokens: where the requests really
        # resumed (the deepest snapshot at or before: ``cached_tokens``
        # counts these); recomputed_tokens: the difference, computed
        # again. snapshots: states handed to the tree; evictions: states
        # the tree gave up under pressure on the slots alone.
        self.state_stats = None
        if self.pool.state is not None:
            self.state_stats = {
                "slots": self.pool.state.shape[1],
                "slot_bytes": self.pool.state_slot_bytes,
                "snapshots": 0,
                "snapshots_declined": 0,
                "snapshots_skipped": 0,
                "kv_matched_tokens": 0,
                "resumed_tokens": 0,
                "recomputed_tokens": 0,
            }
            self.log.info(
                "state slots beside the rows: %d x %d B a snapshot",
                self.pool.state.shape[1], self.pool.state_slot_bytes)
        # matrix_stats: a model whose layers carry a float32 matrix a head
        # (``cfg.matrix_state``: kda linear attention, Mamba-2; None
        # otherwise; ``/debug/state`` publishes it under the kind's name),
        # what its launches move of the state slots, by host arithmetic on
        # the launched shapes. decode_rows: live rows of decode launches;
        # chunk_tokens: new tokens of prefill launches; state_bytes_read /
        # _written: whole snapshots (every such layer's matrix and
        # convolution inputs) the launches read and wrote — a decode launch
        # reads and writes every row of the launched batch (idle rows: the
        # scratch slot) and writes one more for each boundary copy, a
        # prefill launch reads one a row and writes ``_STATE_SNAPS`` a row;
        # boundary_copies: rows of decode launches whose token completed a
        # page and whose state was kept in the boundary slot.
        self.matrix_stats = None
        if cfg.matrix_state:
            self.matrix_stats = {
                "decode_rows": 0, "chunk_tokens": 0, "state_bytes_read": 0,
                "state_bytes_written": 0, "boundary_copies": 0}
        # window: a model whose window layers keep a group of rows of their
        # own (None otherwise). pages_released: pages a live row gave up
        # because they left its window; slots_written: rows the launches
        # wrote there; slots_held_by_rows / held_peak_row: what the rows of
        # the last decode launch held, summed, and the most any row of any
        # launch held; tail_slots_in_tree: rows the tree's snapshots count
        # on. The slots a request is about to resume from are pinned
        # against eviction until its first chunk has read them.
        self.window_stats = None
        self._resuming: collections.Counter = collections.Counter()
        # Snapshots of megabytes (a window tail, a kda layer's matrices, the
        # states of two dozen mamba layers) are given up in order —
        # superseded ones first, never one a request is about to resume
        # from — and a few at a time.
        self._ordered_evict = bool(
            cfg.matrix_state or cfg.has_ssm or self.pool.window is not None)
        if self.pool.window is not None:
            self.window_stats = {
                "slots": self.pool.window_slots,
                "pages_released": 0,
                "slots_written": 0,
                "slots_held_by_rows": 0,
                "held_peak_row": 0,
                "tail_slots_in_tree": 0,
            }
            # context tokens x reading layers a decode step: the full
            # layers' K/V, each read by itself (and, in a cross-decoder, by
            # every cross-attention layer once more)
            self._kv_readers = (
                cfg.layer_types.count("full_attention")
                + cfg.layer_types.count("cross_attention"))
            self._kv_reads = (
                "shared_kv_tokens_read" if cfg.cross_decoder else "full_tokens_read")
            self.attn_window[self._kv_reads] = 0
            gib = 2.0 ** 30
            states = [a for a in (self.pool.state, self.pool.state_conv)
                      if a is not None]
            self.log.info(
                "two groups of rows: full %d slots x %d B = %.2f GiB (%d "
                "reading layers), window %d slots x %d B = %.2f GiB; %d state "
                "slots x %d B = %.2f GiB",
                self.pool.num_slots, self.pool.kv.nbytes // self.pool.num_slots,
                self.pool.kv.nbytes / gib, self._kv_readers,
                self.pool.window_slots,
                self.pool.window.nbytes // self.pool.window_slots,
                self.pool.window.nbytes / gib,
                states[0].shape[1] if states else 0,
                self.pool.state_slot_bytes, sum(a.nbytes for a in states) / gib)
        # tail: a model that keeps a window group and NO state — what the
        # tree holds for a resume is a window tail alone (counts on pages,
        # no copy). matches: requests whose rows the tree held; resumes:
        # those that resumed at a tail (the deepest at or before their
        # match); kv_matched / resumed / recomputed_tokens as ``state``'s;
        # snapshots: tails handed to the tree (evictions: the tree's own
        # count, on ``/debug/state``).
        self.tail_stats = None
        if self.pool.window is not None and self.pool.state is None:
            self.tail_stats = {
                "matches": 0, "resumes": 0, "kv_matched_tokens": 0,
                "resumed_tokens": 0, "recomputed_tokens": 0,
                "snapshots": 0, "snapshots_declined": 0,
            }
        # A prefix is served only from a resume point (a state, a tail).
        self._resumes = (
            self.state_stats is not None or self.tail_stats is not None)
        self._kv_tier_watermark = float(kv_tier_watermark)
        self._kv_tier_min_heat = float(kv_tier_min_heat)
        self._kv_tier_destage_budget = int(kv_tier_destage_budget)
        # Destage cadence: the candidate walk is O(tree nodes) of
        # engine-thread Python, so it runs at most this often — not
        # per scheduler step (durability lags pressure by at most one
        # interval, which the commit-by-rename discipline tolerates).
        # 0 = every pump (tests/drills that need deterministic spills).
        self._kv_tier_destage_interval_s = float(kv_tier_destage_interval_s)
        self._kv_tier_last_destage = 0.0
        # Async KV-movement plane (cache/kv_transfer.py): host-tier
        # restores stage off the scheduling thread (requests park in
        # RESTORING while decode keeps stepping), eviction write-backs
        # materialize on the plane worker, and PREFETCH hints start
        # restores before their request arrives. Off by default — the
        # synchronous paths remain the behavior every existing test pins.
        self.kv_transfer = None
        self._kv_min_restore = max(0, kv_transfer_min_restore_tokens)
        self._restoring: list[tuple[Request, object]] = []
        # Graceful drain (policy/lifecycle.py, set via the runner's
        # begin_drain): PREFETCH hints stop converting to restores — a
        # warm-up nobody will be routed here to use must not open
        # tickets on a departing node.
        self.draining = False
        if kv_transfer_async:
            from radixmesh_tpu.cache.kv_transfer import KVTransferPlane

            self.kv_transfer = KVTransferPlane(
                chunk_tokens=kv_transfer_chunk_tokens,
                name=self.name,
            )
            if hasattr(self.tree, "host"):
                self.tree.plane = self.kv_transfer
        # Cold-cell resurrection (cache/kv_tier.py): scan the extent
        # directory, drop torn/corrupt extents, graft the verified
        # paths back as disk-resident nodes — the node serves its
        # pre-crash working set from disk even when every replica died.
        # Boot-time cold path (file I/O never runs on the serving path).
        self.resurrected = {"extents": 0, "grafted_nodes": 0,
                            "grafted_tokens": 0, "orphaned": 0, "keys": []}
        if self._kv_tier is not None:
            self.resurrected = self.tree.resurrect_from_disk()
        # Reserved scratch page: inactive decode rows write/read here.
        scratch = self.pool.alloc(page_size)
        assert scratch is not None
        self._scratch_slot = int(scratch[0])
        self._scratch_page = self._scratch_slot // page_size

        self.waiting: list[Request] = []
        # SLO seam (radixmesh_tpu/slo/runner.py): invoked with the request
        # right after its first token is recorded — the control plane's
        # prefill service-rate feedback. None = no control plane.
        self.on_first_token = None
        # Pressure latch: set on preemption, cleared when a request finishes
        # (or the batch drains). While set, admission pauses so the
        # surviving rows run to completion instead of the preempted request
        # re-admitting into the freed row and thrashing the pool forever.
        self._pressure = False
        self._rows: list[Request | None] = [None] * max_batch
        self._tokens = np.zeros(max_batch, dtype=np.int32)
        # One backing buffer, width padded to the KV block (the chunked
        # launches' blockwise attention requires it); _page_table is the
        # live [:, :max_pages] view every in-place write flows through,
        # so pp decode can pass the padded buffer without per-step copies
        # (the scratch tail never changes).
        maxp_b = _pow2_at_least(self.max_pages, floor=_KV_BLOCK_PAGES)
        self._page_table_padded = np.full(
            (max_batch, maxp_b), self._scratch_page, dtype=np.int32
        )
        self._page_table = self._page_table_padded[:, : self.max_pages]
        # The window group's table, same geometry (page 0 is its scratch).
        self._w_page_table_padded = (
            None if self.pool.window is None
            else np.zeros((max_batch, maxp_b), dtype=np.int32))
        self._temps = np.zeros(max_batch, dtype=np.float32)
        self._top_ps = np.ones(max_batch, dtype=np.float32)
        self._top_ks = np.zeros(max_batch, dtype=np.int32)
        self._rng = jax.random.PRNGKey(rng_seed)
        # Mid-decode publish cadence (crash recovery, server/recovery.py):
        # every N generated tokens the request's grown prefix publishes
        # to the tree AND the mesh — so surviving replicas hold
        # prompt+generated-so-far and a resurrected request's re-prefill
        # is a near-pure cache hit instead of a full recompute. 0 = only
        # publish at finish/preempt (the pre-recovery behavior).
        self.stream_publish_tokens = stream_publish_tokens
        self.stats = EngineStats()

        reg = get_registry()
        lbl = {"engine": self.name}
        self._m_prompt = reg.counter(
            "radixmesh_engine_prompt_tokens_total",
            "prompt tokens admitted",
            ("engine",),
        ).labels(**lbl)
        self._m_cached = reg.counter(
            "radixmesh_engine_cached_tokens_total",
            "prompt tokens served from the radix cache",
            ("engine",),
        ).labels(**lbl)
        self._m_generated = reg.counter(
            "radixmesh_engine_generated_tokens_total",
            "tokens produced by decode",
            ("engine",),
        ).labels(**lbl)
        self._m_preempt = reg.counter(
            "radixmesh_engine_preemptions_total",
            "requests preempted under pool pressure",
            ("engine",),
        ).labels(**lbl)
        self._m_spec_proposed = reg.counter(
            "radixmesh_engine_spec_proposed_tokens_total",
            "draft tokens offered to speculative verification",
            ("engine",),
        ).labels(**lbl)
        self._m_spec_accepted = reg.counter(
            "radixmesh_engine_spec_accepted_tokens_total",
            "draft tokens accepted by speculative verification",
            ("engine",),
        ).labels(**lbl)
        self._m_spec_rejected = reg.counter(
            "radixmesh_engine_spec_rejected_tokens_total",
            "draft tokens rejected by speculative verification "
            "(conservation: proposed == accepted + rejected)",
            ("engine",),
        ).labels(**lbl)
        self._m_ttft = reg.histogram(
            "radixmesh_engine_ttft_seconds",
            "submit-to-first-token latency",
            ("engine",),
        ).labels(**lbl)
        self._m_tpot = reg.histogram(
            "radixmesh_engine_tpot_seconds",
            "batched decode step latency (== per-token latency for each "
            "active request)",
            ("engine",),
        ).labels(**lbl)
        # Per-TENANT request latency (unlike the per-engine families
        # above): the series a fleet collector (obs/aggregator.py)
        # merges bucket-by-bucket across nodes so /cluster/slo reports
        # the TRUE fleet p50/p99 per tenant — never an average of
        # per-node percentiles. Observed with the request's trace id as
        # exemplar, so a fleet p99 outlier links to its stitched trace.
        self._m_req_ttft = reg.histogram(
            "radixmesh_request_ttft_seconds",
            "submit-to-first-token latency per tenant (fleet-mergeable "
            "buckets; exemplars carry trace ids)",
            ("tenant",),
        )
        self._m_req_e2e = reg.histogram(
            "radixmesh_request_e2e_seconds",
            "submit-to-finish latency per tenant (fleet-mergeable "
            "buckets; exemplars carry trace ids)",
            ("tenant",),
        )
        self._m_hit_len = reg.histogram(
            "radixmesh_engine_prefix_hit_tokens",
            "prefix-cache hit length per admitted request (tokens)",
            ("engine",),
            buckets=TOKEN_LEN_BUCKETS,
        ).labels(**lbl)
        # Evictions by cause (obs/fleet_plane.py registration point): the
        # engine owns capacity (admission pressure) and preempt
        # (mid-decode pressure); the mesh replica owns ttl/mesh_trim.
        self._m_evicted = eviction_counters(self.name)
        # Decode step-time EWMA (seconds per token) — the fleet digest's
        # latency signal; the histogram keeps the full distribution.
        self._decode_ewma = 0.0
        # Per-shape speculative acceptance (prompt-length bucket →
        # [proposed, accepted] draft tokens): the doctor's
        # spec-efficiency rule and the ROADMAP item 1(a) adaptive-γ EWMA
        # both need acceptance BY REQUEST CLASS, which the engine-wide
        # counters above flatten away. Scheduler-thread-only writes
        # (both spec sites run inside _decode_spec).
        self._spec_shape: dict[str, list[int]] = {}
        # Token-level speed plane (obs/token_timeline.py): the per-token
        # ITL ring + stall attribution, the per-class speculation ledger
        # (and its adaptive-γ controller, off unless spec_adaptive), and
        # the per-tenant goodput decomposition. The timeline/goodput pair
        # keeps the one-branch-when-off contract in _flush_emit; the
        # ledger always exists — spec counting must stay conserved
        # whether or not anyone is watching.
        from radixmesh_tpu.obs.token_timeline import (
            GoodputLedger, SpecLedger, TokenTimeline,
        )

        self.spec_ledger = SpecLedger(adaptive=spec_adaptive, node=self.name)
        self.timeline = None
        self.goodput = None
        if token_timeline_capacity > 0:
            self.timeline = TokenTimeline(
                capacity=token_timeline_capacity,
                stall_threshold_s=token_stall_threshold_s,
                node=self.name,
            )
            self.goodput = GoodputLedger(node=self.name)
        # Stall-attribution hints: the instant the last WHOLE prefill
        # wave launched (prefill_convoy), the instant the last INLINE
        # prefill chunk rode a mixed wave (prefill_inline — distinct on
        # purpose: the bounded mitigation must not read as the convoy it
        # replaces, nor as an unexplained scheduler_wait), and a one-shot
        # cause latch external planes set via hint_stall()
        # (rebalance_handoff).
        self._last_prefill_t = 0.0
        self._last_inline_prefill_t = 0.0
        self._stall_hint: str | None = None
        # Mixed-wave state (engine/waves.py): the inline prefill backlog
        # — requests that acquired slots + a batch row but advance their
        # prefill chunk-by-chunk inside decode waves — and the rows they
        # reserve (kept OUT of _rows until install so every decode-path
        # iteration over _rows stays oblivious to them).
        self._inline: list[_InlineJob] = []
        self._inline_rows: set[int] = set()
        # The admission group whose prefill is in flight: its members
        # have left ``waiting`` and not yet installed into ``_rows``, so
        # this is the only handle ``cancel_all`` has on them if the
        # prefill launch raises.
        self._admitting: list[tuple] = []
        self.waves = None
        if self.prefill_inline_budget > 0:
            from radixmesh_tpu.engine.waves import WaveScheduler

            self.waves = WaveScheduler(
                inline_budget=self.prefill_inline_budget,
                max_defer=self.prefill_inline_max_defer,
                chunk=self.prefill_chunk,
                boost_tokens=self.prefill_wave_tokens,
                node=self.name,
            )
        # Request-flight tracing lane for engine-scope (not per-request)
        # events: evictions, preemption sweeps (obs/trace_plane.py).
        self._trace_lane = f"engine:{self.name}"
        # TPU step attribution (obs/step_plane.py): per-wave tokens,
        # pad fraction, and an analytic-FLOPs MFU estimate. OFF by
        # default — the wave hot paths keep the one-branch-when-off
        # contract (a single `is not None` test per wave).
        self.step_acct = None
        # The launched shapes (rows, chunk) of the LAST sub-wave, noted by
        # whichever prefill path ran (single scheduler thread): the launch
        # SHAPES live inside each path, so this is how the wave accounting
        # in _admit learns them without re-deriving bucket math.
        self._wave_shapes: list[tuple[int, int]] = []
        # What the wave planner weighs (engine/prefill_plan.py), from the
        # weights this engine holds: a launch streams every leaf but the
        # embedding table (a gather), a token meets every parameter but,
        # of the experts held, those it is not routed to. Under pp a launch
        # is a pipeline schedule of its own: the plan stays the box.
        leaves = jax.tree_util.tree_flatten_with_path(self.params)[0]
        streamed = [
            leaf for path, leaf in leaves
            if hasattr(leaf, "shape")
            and (cfg.tie_embeddings or "embed" not in jax.tree_util.keystr(path))
        ]
        met = sum(
            leaf.size * (cfg.n_experts_per_tok / cfg.n_experts
                         if cfg.n_experts and leaf.ndim == 4 else 1.0)
            for leaf in streamed)
        self.prefill_cost = launch_cost(
            sum(leaf.size * leaf.dtype.itemsize for leaf in streamed),
            2.0 * met)
        # How wide a chunk launch may be: the power of two that holds the
        # floor, where the chunk kernels this model runs admit it
        # (``prefill_width`` also holds it within the wave's tokens). A
        # model that resumes keeps the chunk: its launches of several rows
        # take ONE table width, so rows of every bucket share the
        # ``[B, chunk]`` programs a burst of short rows compiled — a turn
        # that resumes 2 k tokens back among them — and a wider chunk for
        # the long buckets would be a program no warm-up has sent.
        self._chunk_width = prefill_chunk
        if self._pp:
            self.prefill_cost = (float("inf"), self.prefill_cost[1])
        elif not self._resumes:
            self._chunk_width = chunk_width(
                prefill_chunk, self.prefill_cost[0], prefill_wave_tokens,
                self._chunk_admits)
        if step_accounting:
            from radixmesh_tpu.obs.step_plane import StepAccounting

            n_params = sum(
                int(np.prod(p.shape))
                for p in jax.tree_util.tree_leaves(self.params)
                if hasattr(p, "shape")
            )
            self.step_acct = StepAccounting(
                self.name, n_params, peak_tflops=peak_tflops
            )

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def make_request(
        self,
        prompt: Sequence[int],
        sampling: SamplingParams | None = None,
        *,
        tenant: str = "default",
        ttft_deadline_s: float | None = None,
        e2e_deadline_s: float | None = None,
        resume_tokens: Sequence[int] | None = None,
        trace_id: int | None = None,
    ) -> Request:
        """Build + validate a request WITHOUT queueing it — the admission
        seam the SLO control plane (``radixmesh_tpu/slo/``) holds requests
        behind before deciding to :meth:`enqueue` or shed them.

        ``resume_tokens`` switches on **resume admission** (crash
        recovery, ``server/recovery.py``): the tokens are output a prior
        life of this request already delivered to its client. They are
        appended to the prompt — so prefill replays them against the
        radix cache (a near-pure hit when the crashed node's publishes
        replicated) and the first sampled token is the CONTINUATION at
        position ``len(prompt)+len(resume_tokens)`` — but they are never
        re-emitted: ``output_tokens`` starts empty and
        ``sampling.max_new_tokens`` is debited by the tokens already
        delivered, so the request's total output budget is unchanged
        across lives."""
        sampling = sampling or SamplingParams()
        prompt = np.asarray(prompt, dtype=np.int32)
        resume_offset = 0
        if resume_tokens is not None and len(resume_tokens) > 0:
            resume = np.asarray(resume_tokens, dtype=np.int32)
            resume_offset = len(resume)
            if resume_offset >= sampling.max_new_tokens:
                # The delivered tokens already cover the whole output
                # budget: there is NOTHING to resume, and admitting
                # would sample a token the first life never would have
                # (output past the requested cap). The edge holds the
                # complete stream — refuse loudly instead.
                raise ValueError(
                    f"resume_tokens ({resume_offset}) cover the full "
                    f"max_new_tokens budget ({sampling.max_new_tokens}) "
                    "— the stream is already complete"
                )
            prompt = np.concatenate([prompt, resume])
            sampling = dataclass_replace(
                sampling,
                max_new_tokens=sampling.max_new_tokens - resume_offset,
            )
        req = Request(
            prompt=prompt,
            sampling=sampling,
            tenant=tenant,
            ttft_deadline_s=ttft_deadline_s,
            e2e_deadline_s=e2e_deadline_s,
            resume_offset=resume_offset,
        )
        if not (0 < len(req.prompt) < self.max_seq_len):
            raise ValueError(f"prompt length {len(req.prompt)} out of range")
        if self.window_stats is not None:
            # A prefill holds the window pages of its whole uncached span
            # until it installs: a prompt the group cannot hold would wait
            # at the head of the queue for ever.
            have = self.pool.window_slots - self.page_size  # the scratch page
            if len(req.prompt) > have:
                raise ValueError(
                    f"prompt length {len(req.prompt)} exceeds the window "
                    f"group's {have} slots")
        if resume_offset:
            self.stats.resurrections += 1
            # The whole resumed prompt is replay: the original prompt AND
            # the delivered tokens all re-prefill on the new node.
            self.stats.replayed_tokens += len(prompt)
        req.submit_time = time.monotonic()
        # Request-flight tracing (obs/trace_plane.py): returns None when
        # tracing is off or the request lost the sampling coin flip —
        # every downstream span site is then one `is not None` branch.
        # ``trace_id`` ADOPTS an upstream node's 64-bit id (a resume or
        # hedge re-route carries it in the /generate body, PR 9 cross-
        # node stitching), so this node's spans land in the originating
        # request's timeline instead of under a fresh id.
        req.trace = get_recorder().trace(
            f"req:{req.rid}", trace_id=trace_id, node=self.name
        )
        return req

    def enqueue(self, req: Request) -> Request:
        """Hand a built request to the scheduler queue."""
        self.waiting.append(req)
        return req

    def add_request(
        self,
        prompt: Sequence[int],
        sampling: SamplingParams | None = None,
        **kw,
    ) -> Request:
        return self.enqueue(self.make_request(prompt, sampling, **kw))

    def cancel(self, rid: int) -> bool:
        """Abort a queued or running request. Running requests release
        their batch row; KV computed so far publishes to the radix cache
        as usual (it is a valid prefix for future hits). The request
        finishes with whatever output it had — callers check
        ``req.cancelled``. Returns False for unknown/finished rids.
        NOT thread-safe against a concurrent ``step``; serialize through
        the owner (``server/http_frontend.py::EngineRunner.cancel``)."""
        for i, req in enumerate(self.waiting):
            if req.rid == rid:
                self.waiting.pop(i)
                req.cancelled = True
                req.state = RequestState.FINISHED
                self.stats.finished += 1
                return True
        for req in self._rows:
            if req is not None and req.rid == rid:
                self._settle("cancel")  # its last token is part of its output
                if req.state is RequestState.FINISHED:
                    return False  # that token took it to its length cap
                req.cancelled = True
                self._flush_emit(_EXPOSED, only=req)
                req.state = RequestState.FINISHED
                self.stats.finished += 1
                self._release(req)
                self._pressure = False  # freed a row: resume admission
                return True
        for i, (req, ticket) in enumerate(self._restoring):
            if req.rid == rid:
                # Cancel mid-restore: unlink the request; the ticket runs
                # to completion (the landed KV is a valid warm cache
                # entry) and the pump auto-releases its eviction shields,
                # so the protected pages become evictable again.
                req.cancelled = True
                req.state = RequestState.FINISHED
                self.stats.finished += 1
                ticket.auto_release = True
                self._restoring.pop(i)
                return True
        for i, job in enumerate(self._inline):
            if job.req.rid == rid:
                # Cancel mid-inline-prefill: the job never installed, so
                # nothing published — release the row reservation, the
                # prefix lock, and the acquired pages (partially-written
                # chunk KV is discarded with them).
                self._inline.pop(i)
                self._inline_rows.discard(job.row)
                req = job.req
                if job.own.size:
                    self.pool.free(job.own)
                if req.lock_node is not None:
                    self.tree.dec_lock_ref(req.lock_node)
                    req.lock_node = None
                req.cancelled = True
                req.state = RequestState.FINISHED
                self.stats.finished += 1
                return True
        for i, (req, _row, _reuse, _prefix, own) in enumerate(self._admitting):
            if req.rid == rid and req.state is RequestState.QUEUED:
                # The group's prefill raised before this member
                # installed (installed members were found in _rows
                # above): same release as an inline job — pages, prefix
                # lock, nothing published.
                self._admitting.pop(i)
                if own.size:
                    self.pool.free(own)
                self._free_states(req)
                if req.lock_node is not None:
                    self.tree.dec_lock_ref(req.lock_node)
                    req.lock_node = None
                req.cancelled = True
                req.state = RequestState.FINISHED
                self.stats.finished += 1
                return True
        return False

    def cancel_all(self, error: str | None = None) -> int:
        """Abort every request the engine holds — queued, running,
        parked, mid-prefill (shutdown sweep; with ``error``, the sweep
        after a step raised: each request carries the message to its
        client). Returns the number cancelled."""
        self._settle("cancel")
        self._flush_emit(_EXPOSED)  # no step may follow (shutdown, a failed one)
        reqs = (
            list(self.waiting)
            + [r for r in self._rows if r is not None]
            + [r for r, _ in self._restoring]
            + [j.req for j in self._inline]
            + [m[0] for m in self._admitting]
        )
        for req in reqs:
            req.error = error
        n = sum(1 for req in reqs if self.cancel(req.rid))
        self._admitting = []
        return n

    # ------------------------------------------------------------------
    # graceful drain (policy/lifecycle.py, serialized via the runner)
    # ------------------------------------------------------------------

    def drain_requeue_waiting(self) -> int:
        """Cancel-and-flag every QUEUED and parked-RESTORING request for
        requeue at the router: they have produced nothing, so bouncing
        them to a surviving node loses no work — while RUNNING rows are
        deliberately left alone to finish under the drain deadline.
        The ``drain_requeue`` shed reason tells the client (and the
        chaos workload) to resubmit via the router, not give up.
        Restore tickets flip to auto-release (the existing cancel path),
        so no eviction shield outlives the departing request.
        Mid-inline-prefill requests count too: they have not produced a
        token either (only partial KV, discarded by cancel), so bouncing
        them loses at most one chunk of compute."""
        self._settle()
        victims = (
            list(self.waiting)
            + [r for r, _ in self._restoring]
            + [j.req for j in self._inline]
        )
        n = 0
        for req in victims:
            req.shed = True
            req.shed_reason = "drain_requeue"
            if self.cancel(req.rid):
                n += 1
        return n

    def drain_flush_hot(self) -> int:
        """Write every unlocked device-resident prefix back to the host
        tier — the PR 4 fused write-back lane does the moving (one
        gather per sweep; arena writes land on the plane worker) — so a
        warm rejoin, or a sibling's restore, finds the working set
        instead of recomputing it. Returns tokens written back; 0
        without a host tier. Run AFTER in-flight decodes finish: evict
        only touches unlocked entries, so flushing early would silently
        skip everything a running request still pins."""
        self._settle()
        tree = self.tree
        if getattr(tree, "host", None) is None:
            return 0
        total = 0
        while True:
            n = tree.evictable_size_
            if n <= 0:
                break
            freed = tree.evict(n)
            if freed <= 0:
                break
            total += freed
        return total

    def drain_flush_disk(self, timeout_s: float = 30.0) -> tuple[int, bool]:
        """Drain step: flush hot subtrees DISK-ward — force-destage
        every host-resident prefix to checksummed extents and wait for
        the commits, so the working set survives even if the whole cell
        (this node included) later loses power before a rejoin. Returns
        ``(spills submitted, all committed)``; (0, True) without a
        tier. Run after :meth:`drain_flush_hot` so the device flush has
        landed in the arena first."""
        self._settle()
        tree = self.tree
        plane = self.kv_transfer
        if self._kv_tier is None or plane is None:
            return 0, True
        submitted = tree.destage_cold(force=True, budget=1 << 30)
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            plane.pump(tree)  # spill commits land on this (engine) thread
            if plane.spills_idle():
                return submitted, True
            plane.wait_progress(0.02)
        plane.pump(tree)
        return submitted, plane.spills_idle()

    def announce_resurrected(self) -> int:
        """Re-announce resurrected prefixes through the existing
        bootstrap/SHARD_SUMMARY path: each grafted key re-enters the
        mesh tree via the normal insert (owner-addressed under
        sharding), so summaries, fingerprints, and pull-through routing
        advertise the disk-resident working set exactly like a live
        one. Call after the mesh transport is up. Returns keys
        announced."""
        mesh = self.mesh
        keys = self.resurrected.get("keys") or []
        if mesh is None or not keys:
            return 0
        n = 0
        for key in keys:
            key = np.asarray(key, dtype=np.int32)
            if len(key) == 0:
                continue
            # Advertisement-only insert (AdvertisedValue): replicas
            # store origin-rank tags anyway, this node serves the
            # prefix through a staged disk restore at admission time,
            # and the placeholder indices are never pool-freed.
            mesh.insert(
                key, np.arange(len(key), dtype=np.int32), advertise=True
            )
            n += 1
        return n

    def step(self) -> None:
        """One scheduler iteration — ONE compute wave. Legacy schedule
        (``prefill_inline_budget == 0``): admit+prefill queued requests
        to completion, then one batched decode step for everything
        running. Mixed schedule (budget > 0, engine/waves.py): while
        decode rows are running, admission parks new prompts in the
        inline backlog and each wave packs the decode step PLUS a
        budget-bounded slice of their chunked prefill into a single
        fused launch — long prompts advance between decode steps instead
        of convoying them.

        A decode step's tokens are in ``output_tokens`` when the step
        returns or, between two plain decode launches, once the NEXT
        launch is on the device: such a step leaves its per-row advance
        pending (``_decode_once``), and the next step sends its launch —
        built behind this one — before it advances this step's rows. A
        row that this step takes to its length cap (``max_new_tokens``,
        ``max_seq_len``: known before the launch is sent) is no row of
        that next launch, and is finished, released and published behind
        it, with the advance: until then it sits in its row, RUNNING. A
        step in which a row samples one of its stop ids or after which
        no row goes on, and every launch that is not the plain one,
        advances its rows before it returns; :meth:`_settle` makes it so
        at any time, and everything that reads or changes the rows calls
        it first. The tokens' stream wake-ups, timeline entries and
        output counter (:meth:`_flush_emit`) run in the next step too,
        once its launch is on the device — or at the end of this one
        when it leaves no row to decode. First tokens wake their waiters
        at once, a finish when its advance runs.

        Host spans (``self.spans``: each a profiler annotation, inert
        without a capture, and an always-on count and seconds):
        ``rm.engine.step`` ⊃ ``rm.admit`` ⊃ {``rm.tree.match``,
        ``rm.prefill.dense`` / ``rm.prefill.group``,
        ``rm.first_token.sync``}, then ``rm.decode.build`` / ``.dispatch``
        / ``.sample`` / ``.emit`` / ``.sync`` / ``.consume`` (⊃
        ``rm.tree.publish``) — the same names in every decode launch
        path, so a reader of a capture never needs to know which ran. A
        fast step reads ``dispatch, sample, consume`` (the step
        before's, ``rm.tree.publish`` inside it where that step took a
        row to its length cap), ``build`` (the next launch's), ``emit,
        sync``; a step that settles ends ``sync, consume``."""
        launched = self.prefill["tokens_launched"]
        acct = self.process_account
        c = acct and acct.compiles
        pauses = acct and (
            acct.gc_n, acct.gc_s, c["n"], c["s"], c["trace_s"], c["lower_s"],
            acct.stored["hits"], acct.stored["load_s"],
        )
        kind = "idle"
        with self.spans.step() as span:
            with self.spans.span("rm.admit"):
                self._admit()
            if self._inline:
                kind = "wave"
                self._wave_step()
            elif any(r is not None for r in self._rows):
                kind = "decode"
                self._decode_once()
            elif not self.waiting and (
                self._restoring
                or (
                    self.kv_transfer is not None
                    and self.kv_transfer.has_engine_work()
                )
            ):
                # Nothing to decode and nothing admittable: the only live
                # work is an in-flight restore (a parked request's or a
                # prefetch hint's) — yield to the plane worker instead of
                # busy-spinning the scheduler loop against it.
                self.kv_transfer.wait_progress()
            if not any(r is not None for r in self._rows):
                # No decode launch will follow to hide the emission under.
                self._settle()
                self._flush_emit(_EXPOSED)
        # A step may honestly take seconds (a group of long prompts is
        # many chunk launches inside one _admit), so the test is on the
        # excess over its own prefill at the running rate.
        p = self.prefill
        launched = p["tokens_launched"] - launched
        excess = span.seconds
        if launched:
            excess -= launched * p["wall_s"] / p["tokens_launched"]
        if excess > _SLOW_STEP_S:
            self._note_slow_step(span, excess, kind, launched, pauses)

    def _note_slow_step(self, span, excess, kind, launched, pauses) -> None:
        """One record of ``slow_steps``: when, how long, the three spans
        that took most of the step, what it launched, the queue, the
        collections, compilations and loads of stored programs that fell
        inside it (where a ``process_account`` is installed) and the
        device's memory, read only here. It does not say what the runtime did in those seconds;
        it says that this run had a stall, when, under which span and at
        what memory."""
        from radixmesh_tpu.obs.step_plane import describe_devices

        slow = self.slow_steps
        slow["n"] += 1
        slow["s"] += excess
        record = {
            "at_s": round(since_import(), 3),
            "wall_time": round(time.time(), 3),
            "step_s": round(span.seconds, 6),
            "excess_s": round(excess, 6),
            "spans": self.spans.top_since(span.entry),
            "launch": kind,
            "prefill_tokens_launched": launched,
            "rows": sum(1 for r in self._rows if r is not None),
            "waiting": len(self.waiting),
            "memory": [
                {k: m[k] for k in ("bytes_in_use", "peak_bytes_in_use")}
                for m in describe_devices()["memory"]
            ],
        }
        if pauses:
            acct = self.process_account
            gc_n, gc_s, c_n, c_s, c_trace_s, c_lower_s, loads, load_s = pauses
            c = acct.compiles
            record["gc"] = {"n": acct.gc_n - gc_n, "s": round(acct.gc_s - gc_s, 6)}
            record["compiles"] = {
                "n": c["n"] - c_n,
                "s": round(c["s"] - c_s, 6),
                "trace_s": round(c["trace_s"] - c_trace_s, 6),
                "lower_s": round(c["lower_s"] - c_lower_s, 6),
                # programs loaded from the store: no compilation, a pause
                "loads": acct.stored["hits"] - loads,
                "load_s": round(acct.stored["load_s"] - load_s, 6),
            }
        slow["last"].append(record)

    @property
    def tree_host(self) -> dict:
        """Host seconds in the tree walk + slot acquisition and in
        ``_publish``: the book's ``rm.tree.match`` / ``rm.tree.publish``
        under the four keys ``/debug/state`` ``cache.tree_host`` has."""
        n, s = self.spans.n, self.spans.s
        return {
            "matches": n["rm.tree.match"],
            "match_s": s["rm.tree.match"],
            "publishes": n["rm.tree.publish"],
            "publish_s": s["rm.tree.publish"],
            "publish_key_tokens": self.tree_publish["key_tokens"],
            "publish_walked_tokens": self.tree_publish["walked_tokens"],
        }

    def has_work(self) -> bool:
        return (
            bool(self.waiting)
            or self._pending is not None
            or bool(self._inline)
            or bool(self._restoring)
            or any(r is not None for r in self._rows)
            or (
                self.kv_transfer is not None
                and self.kv_transfer.has_engine_work()
            )
        )

    def _wave_step(self) -> None:
        """Run one wave while the inline backlog is non-empty: ask the
        wave scheduler for the wave's composition, execute it, commit
        the defer/metric accounting. Decode-bearing plans fuse the
        inline chunks into the decode launch itself; prefill/boost
        plans advance the backlog alone (and count against the
        starvation bound when decode rows are waiting)."""
        self._settle()
        decode_rows = sum(1 for r in self._rows if r is not None)
        plan = self.waves.plan(
            decode_rows, [j.total - j.pos for j in self._inline]
        )
        if plan.decode and decode_rows:
            if self._seeded_launch(self._rows):
                # All-seeded batches keep the canonical per-row
                # (seed, position) decode launch bit-identical to the
                # legacy path (the replay-determinism contract), so the
                # wave runs as two launches: decode, then the budgeted
                # inline slice.
                self._decode_once()
                self._inline_advance(plan.allot)
            else:
                self._decode_once(inline_allot=plan.allot)
        else:
            self._inline_advance(plan.allot)
        self.waves.note(plan)

    def _inline_advance(self, allot: list[int]) -> None:
        """Advance the inline backlog WITHOUT a decode step: prefill and
        boost waves, plus the second launch of the all-seeded fallback.
        Same fused chunk builder, decode disabled."""
        if not any(allot):
            return
        self._decode_spec_once(0, {}, None, inline=allot, decode=False)

    def _note_decode_time(self, per_token_s: float) -> None:
        """Funnel for every decode-latency sample: the TPOT histogram
        keeps the distribution; the EWMA is the fleet digest's compact
        latency signal (obs/fleet_plane.py)."""
        self._m_tpot.observe(per_token_s)
        if self._decode_ewma == 0.0:
            self._decode_ewma = per_token_s
        else:
            self._decode_ewma += 0.2 * (per_token_s - self._decode_ewma)

    def telemetry(self) -> dict:
        """Point-in-time engine signals for the fleet digest
        (``obs/fleet_plane.py::FleetPlane.build_digest``). Lock-free
        snapshot reads, same rationale as the /debug endpoints: a wedged
        engine must still be describable — that is exactly when the
        stall watchdog needs this data."""
        rows = sum(1 for r in self._rows if r is not None)
        host = getattr(self.tree, "host", None)
        host_fill = 0.0
        if host is not None and getattr(host, "num_slots", 0):
            host_fill = 1.0 - host.free_slots / host.num_slots
        return {
            "batch_occupancy": rows / max(1, self.max_batch),
            # Parked-for-restore and inline-prefilling requests count as
            # waiting: they are queued demand the fleet plane should
            # see, just queued on a KV transfer / the wave scheduler's
            # chunk budget instead of a batch row.
            "waiting": len(self.waiting) + len(self._restoring)
            + len(self._inline),
            "decode_steps": self.stats.decode_steps,
            "decode_ewma_s": self._decode_ewma,
            "cache_hit_rate": self.stats.hit_rate,
            "pool_fill": 1.0 - self.pool.fill_free_fraction(),
            "host_fill": host_fill,
            "evictions": {
                c: int(m.value) for c, m in self._m_evicted.items()
            },
            "spec": self.spec_report(),
            # Durable tier occupancy (None without a tier): lock-guarded
            # snapshot reads inside stats().
            "kv_tier": (
                None if self._kv_tier is None else self._kv_tier.stats()
            ),
        }

    def spec_report(self) -> dict:
        """Per-shape speculative acceptance (prompt-length bucket →
        proposed/accepted draft tokens + acceptance rate) — the
        spec-efficiency evidence the doctor's rule and /cluster/telemetry
        surface, and the substrate the item-1(a) adaptive-γ EWMA will
        fold. Snapshot read, same lock-free rationale as telemetry() —
        but unlike telemetry()'s fixed-key dicts, _spec_shape GROWS when
        the scheduler sees a new prompt bucket, so take the one-C-call
        list() snapshot before iterating (a dict comprehension over the
        live dict can raise dictionary-changed-size mid-GET)."""
        cells = list(self._spec_shape.items())
        return {
            shape: {
                "proposed": int(p),
                "accepted": int(a),
                "acceptance": round(a / p, 4) if p else 0.0,
            }
            for shape, (p, a) in sorted(cells)
        }

    def generate(
        self,
        prompts: Iterable[Sequence[int]],
        sampling: SamplingParams | None = None,
        max_steps: int = 100_000,
    ) -> list[list[int]]:
        reqs = [self.add_request(p, sampling) for p in prompts]
        for _ in range(max_steps):
            if not self.has_work():
                break
            self.step()
        self._settle()
        unfinished = [r.rid for r in reqs if r.state is not RequestState.FINISHED]
        if unfinished:
            # A real error, not an assert (VERDICT round-1 weak #5):
            # surfaces under ``python -O`` too, and says which requests
            # and why the loop stopped.
            raise RuntimeError(
                f"generate() exhausted max_steps={max_steps} with requests "
                f"{unfinished} unfinished (pool too small for the workload, "
                f"or a scheduling stall)"
            )
        return [r.generated for r in reqs]

    # ------------------------------------------------------------------
    # admission + prefill
    # ------------------------------------------------------------------

    def _free_row(self) -> int:
        for i, r in enumerate(self._rows):
            # Rows parked behind an inline prefill job hold a batch seat
            # but no Request yet (the install happens on the job's final
            # chunk) — not free.
            if r is None and i not in self._inline_rows:
                return i
        return -1

    def _alloc_pages(self, n_pages: int, cause: str = "capacity") -> np.ndarray | None:
        """Whole-page allocation with evict-under-pressure retry (the
        reference's evict-then-insert flow, ``radix_cache.py:179-202``).
        ``cause`` labels any eviction this allocation forces ("capacity"
        = admission pressure, "preempt" = mid-decode page growth — the
        storm detector and dashboards tell them apart)."""
        n = n_pages * self.page_size
        slots = self.pool.alloc(n)
        if slots is None:
            rec = get_recorder()
            t_ev = time.monotonic() if rec.enabled else 0.0
            want = max(n - self.pool.free_slots,
                       self.pool.num_slots // _EVICT_POOL_SHARE)
            if self.mesh is not None:
                # Eviction that DESTROYS KV must un-advertise the prefix
                # ring-wide — otherwise the router keeps routing
                # shared-prefix requests to a node that can no longer serve
                # them. The hook fires per destroyed node only: host-tier
                # trees invoke it just when write-back fails (a written-back
                # prefix stays servable via restore, so it stays
                # advertised).
                freed = self.tree.evict(want, on_evict=self._unadvertise)
            else:
                freed = self.tree.evict(want)
            if freed:
                self._m_evicted[cause].inc(freed)
            slots = self.pool.alloc(n)
            if rec.enabled:
                rec.event(
                    self._trace_lane, "evict", t_ev,
                    time.monotonic() - t_ev, cat="cache",
                    need_slots=int(n), satisfied=bool(slots is not None),
                )
        return slots

    def _unadvertise(self, node) -> None:
        """Evict hook: release the node's pool slots (``on_evict`` replaces
        the tree's ``on_free`` batch, so freeing is this hook's job) and
        best-effort retract the prefix ring-wide: mesh replicas only apply
        (and replicate) the DELETE when the key lands on an unlocked leaf
        there, so a prefix another node extended survives."""
        self.pool.free(np.asarray(node.value, dtype=np.int32))
        parts = []
        while node is not None and node.parent is not None:
            parts.append(node.key)
            node = node.parent
        if parts:
            self.mesh.delete(np.concatenate(parts[::-1]))

    def _admit(self) -> None:
        """Admit waiting requests into free rows. Concurrent arrivals are
        prefilled together and sampled together (VERDICT round-1 weak #5:
        per-request serial prefill made TTFT degrade linearly with queue
        depth): the admission is cut into sub-waves by bucket of new
        tokens, and ONE rule decides the shape of every launch that
        prefills a sub-wave — ``plan_prefill`` (engine/prefill_plan.py),
        which launches the tokens the rows have where a padded token costs
        more than a launch, by a cost this engine derived from its weights
        (``self.prefill_cost``); a lone request goes the same way, as a
        group of one, and the chunk path reads a cached prefix where it
        lies, in pages. Only a lone request with NOTHING cached whose one
        dense launch is bound by the weights it streams keeps the dense
        single-request path (``_prefill_dense``)."""
        self._pump_kv_transfer()
        if self._pressure and any(r is not None for r in self._rows):
            return
        self._pressure = False  # batch drained: safe to admit again
        made_progress = True
        while self.waiting and made_progress:
            # Mixed compute waves (engine/waves.py): while decode rows
            # are running, acquired prompts park in the inline backlog
            # instead of prefilling here — _wave_step rides their chunks
            # on the decode launches, budget-bounded, so the running
            # streams never see a whole-prefill convoy. With no decode
            # rows (cold start / drained batch) the legacy bulk subwave
            # path below keeps its full-width TTFT.
            mix = self.waves is not None and any(
                r is not None for r in self._rows
            )
            group: list[tuple] = []
            idx = 0
            while idx < len(self.waiting):
                row = self._free_row()
                if row < 0:
                    break
                # A row to fill: whatever is admitted sees the rows, the
                # tree and the pool as every step before it left them.
                self._settle("admit")
                req = self.waiting[idx]
                with self.spans.span("rm.tree.match"):
                    # One tree walk serves both the defer check and acquisition
                    # (match_and_load also restores host-tier KV, so a
                    # restorable prefix never triggers a needless deferral).
                    tr = req.trace
                    t_match = time.monotonic() if tr is not None else 0.0
                    if hasattr(self.tree, "match_and_load"):
                        match = None
                        if self.kv_transfer is not None:
                            match = self.tree.match_prefix(req.prompt)
                            if match.host_nodes or match.disk_nodes:
                                # Disk extensions ALWAYS park (extent reads
                                # are staged-only); host-only extensions
                                # park past the min-restore threshold.
                                if match.disk_nodes or (
                                    match.host_length + match.disk_length
                                    >= self._kv_min_restore
                                ):
                                    if self._park_for_restore(req, match):
                                        self.waiting.pop(idx)
                                        continue  # parked; don't advance idx
                                    # Park failed: begin_restore may have
                                    # EVICTED for room, so the walked match
                                    # can hold stale slots — re-walk.
                                    match = None
                                else:
                                    # Small restore: tree untouched since the
                                    # walk — hand the match to the sync path
                                    # (one walk total, not two).
                                    match = self.tree.match_and_load(
                                        req.prompt, match=match
                                    )
                        if match is None:
                            match = self.tree.match_and_load(req.prompt)
                    else:
                        match = self.tree.match_prefix(req.prompt)
                    if tr is not None:
                        tr.add(
                            "prefix_match",
                            t_match,
                            time.monotonic() - t_match,
                            cached_tokens=int(match.length),
                            prompt_tokens=len(req.prompt),
                        )
                    if self._defer_for_prefix_wave(req, match.length, group):
                        # Admitting this request NOW would recompute a prefix a
                        # groupmate is about to publish; next wave it's a cache
                        # hit instead (the serial-admission sharing the batch
                        # path would otherwise lose).
                        idx += 1
                        continue
                    acquired = self._acquire_prompt_slots(req, match)
                    if acquired is None:
                        break  # pool exhausted even after evict: wait for finishes
                self.waiting.pop(idx)
                # Queue wait: preemption requeue, SLO dispatch, or
                # submission — whichever happened LAST — up to the
                # instant a batch row was secured (a preempted
                # request's first life must not render as queueing).
                # Counted for every admission (self.admission_wait); the
                # sampled flight-recorder span covers the same interval.
                t_start = max(
                    req.requeue_time, req.admit_time, req.submit_time
                )
                waited = time.monotonic() - t_start
                aw = self.admission_wait
                aw["count"] += 1
                aw["sum_s"] += waited
                aw["counts"][
                    bisect.bisect_left(_ADMISSION_WAIT_LE_S, waited)
                ] += 1
                if tr is not None:
                    tr.add("admission_wait", t_start, waited, cat="queue")
                reuse, prefix_slots, own = acquired
                self._rows[row] = req  # reserve the row; re-set on install
                group.append((req, row, reuse, prefix_slots, own))
            for _, row, *_ in group:
                self._rows[row] = None
            made_progress = bool(group)
            if not group:
                break
            self._admitting = group
            if mix:
                for req, row, reuse, prefix_slots, own in group:
                    total = len(req.prompt)
                    self._inline.append(
                        _InlineJob(
                            req=req,
                            row=row,
                            reuse=reuse,
                            own=own,
                            token_slots=np.concatenate(
                                [prefix_slots, own[: total - reuse]]
                            ),
                            pos=reuse,
                            total=total,
                        )
                    )
                    # Reserve the batch seat without a Request in it:
                    # the request stays QUEUED (state-machine-wise it is
                    # still waiting for its first token) until the final
                    # chunk installs it RUNNING.
                    self._inline_rows.add(row)
                self._admitting = []
                continue
            # Sub-waves by prefill-size bucket, shortest first, each sliced
            # at ``prefill_wave_tokens`` (``prefill_plan.subwaves``). Each
            # sub-wave finalizes itself (one batched sample + one
            # device→host sync), so TTFT is bounded by the request's own
            # bucket; WHICH launches prefill it is the planner's
            # (``_prefill_group``).
            cut = subwaves(
                [len(m[0].prompt) - m[2] for m in group],
                chunk=self.prefill_chunk, wave_tokens=self.prefill_wave_tokens,
                width=self.prefill_width)
            for sub in ([group[i] for i in rows] for rows in cut):
                # Quantized pools always prefill through the chunked
                # paged path: it attends the already-quantized K/V
                # (see prefill_chunk_paged), so prefill-time logits
                # match every later read of the published prefix. The
                # dense/sp paths attend full-precision and only
                # quantize at pool.write — fine for bf16 pools, an
                # invariant break for int8.
                # pp engines prefill exclusively through the chunked
                # paged path: it is the pipeline-scheduled one (the
                # dense/sp paths would all-gather stage weights).
                traced = [m[0].trace for m in sub if m[0].trace is not None]
                clock = self.spans.clock
                t_wave = clock()
                self._wave_shapes = shapes = []
                news = [len(m[0].prompt) - m[2] for m in sub]
                # One request with nothing cached, on a pool and a mesh
                # that leave the choice: the dense launch, where it is
                # bound by the weights it streams (see ``_prefill_dense``).
                lone_cold = (
                    self.pool.quant is None
                    and not self._pp
                    and len(sub) == 1
                    and sub[0][2] == 0
                )
                dense_up_to = self.long_prefill_threshold if lone_cold else 0
                boxed = boxed_tokens(
                    news, chunk=self.prefill_chunk, dense_up_to=dense_up_to,
                    width=self.prefill_width)
                if lone_cold and self._sp_capable(sub[0]):
                    path = "sp"
                    pending = [self._prefill_sp(*sub[0])]
                    boxed = shapes[0][0] * shapes[0][1]  # its one launch
                elif (
                    # A cached prefix is read where it lies, in pages (the
                    # chunk path): a dense copy of it would be gathered,
                    # padded and re-laid before every such prefill.
                    news[0] <= dense_up_to
                    and _pow2_at_least(news[0]) <= self.prefill_cost[0]
                ):
                    path = "dense"
                    with self.spans.span("rm.prefill.dense"):
                        req, row, _, _, own = sub[0]  # reuse 0, no prefix slots
                        pending = [self._prefill_dense(req, row, own)]
                else:
                    path = "chunk"
                    with self.spans.span("rm.prefill.group"):
                        pending = self._prefill_group(sub)
                self._last_prefill_path = path
                # The prefill is on the device and its sync comes next:
                # the last decode step's emission goes here.
                self._flush_emit(_UNDER_PREFILL)
                with self.spans.span("rm.first_token.sync"):
                    self._finalize_first_tokens(pending)
                # The wave's work, counted always (self.prefill): real vs
                # launched-shape tokens — each prefill path noted its
                # launches (_wave_shapes) — beside the one box of the same
                # rows, and its wall time. Step attribution
                # (obs/step_plane.py) is fed the same numbers.
                dur = clock() - t_wave
                new_tok = sum(news)
                launched = sum(b * c for b, c in shapes)
                p = self.prefill
                p["waves"] += 1
                p["rows"] += len(sub)
                p["launches"] += len(shapes)
                p["launches_below_floor"] += sum(
                    b * c < self.prefill_cost[0] for b, c in shapes)
                for _, c in shapes:
                    p["by_chunk"][c] = p["by_chunk"].get(c, 0) + 1
                p["tokens_new"] += new_tok
                p["tokens_launched"] += launched
                p["tokens_boxed"] += boxed
                p["wall_s"] += dur
                p["by_path"][path] += 1
                if self.step_acct is not None:
                    self.step_acct.note_wave(
                        "prefill",
                        new_tok,
                        launched,
                        dur,
                        rows=len(sub),
                    )
                # One prefill-wave span per traced member (covers the
                # whole sub-wave through first-token finalize, so each
                # request's lane shows the convoy it rode in).
                for tr in traced:
                    tr.add(
                        "prefill_wave",
                        t_wave,
                        dur,
                        cat="prefill",
                        wave_rows=len(sub),
                        wave_new_tokens=int(new_tok),
                    )
            self._admitting = []

    # ------------------------------------------------------------------
    # async KV-movement plane seams (cache/kv_transfer.py)
    # ------------------------------------------------------------------

    def _pump_kv_transfer(self) -> None:
        """Engine-thread service point for the plane, run at the top of
        every admission pass: apply staged restore scatters (the only
        place the plane touches the donated pool buffer), re-queue parked
        requests whose pages landed, and convert prefetch hints into
        no-request restores."""
        plane = self.kv_transfer
        if plane is None:
            return
        plane.pump(self.tree)
        for key in plane.take_hints():
            self._apply_prefetch_hint(key)
        if self._kv_tier is not None and not self.draining:
            # Write-behind destage (cache/kv_tier.py): past the arena
            # watermark, cold-ish host prefixes spill to disk extents
            # on the plane worker, so later arena pressure DEMOTES
            # (free) instead of DROPPING (data loss). In-memory
            # submission only — file I/O stays off this thread — and
            # cadence-throttled: the candidate walk is O(tree), not
            # per-step work.
            now = time.monotonic()
            if (
                now - self._kv_tier_last_destage
                >= self._kv_tier_destage_interval_s
            ):
                self._kv_tier_last_destage = now
                self.tree.destage_cold(
                    watermark=self._kv_tier_watermark,
                    min_heat=self._kv_tier_min_heat,
                    budget=self._kv_tier_destage_budget,
                )
        if not self._restoring:
            return
        still: list[tuple[Request, object]] = []
        for req, ticket in self._restoring:
            if not ticket.done:
                still.append((req, ticket))
                continue
            plane.finish_ticket(self.tree, ticket)
            req.state = RequestState.QUEUED
            self.waiting.insert(0, req)
            tr = req.trace
            if tr is not None:
                tr.add(
                    "kv_restore", ticket.t0,
                    time.monotonic() - ticket.t0, cat="kv",
                    tokens=int(ticket.tokens),
                )
        self._restoring = still

    def _restore_alloc(self, n_tokens: int) -> np.ndarray | None:
        """Device slots for a staged restore, evicting (plain drop, no
        write-back — see ``evict_no_writeback``) under pressure."""
        dev = self.pool.alloc(n_tokens)
        if dev is None:
            freed = self.tree.evict_no_writeback(
                n_tokens - self.pool.free_slots
            )
            if freed:
                self._m_evicted["capacity"].inc(freed)
            dev = self.pool.alloc(n_tokens)
        return dev

    def _park_for_restore(self, req: Request, match) -> bool:
        """Move ``req`` into the RESTORING state behind a staged-restore
        ticket. Returns False when nothing could be restored (pool
        exhausted even after eviction) — the caller falls back to the
        synchronous path, which degrades to a shorter hit."""
        ticket = self.kv_transfer.begin_restore(
            self.tree, match, alloc=self._restore_alloc
        )
        if ticket is None:
            return False
        req.state = RequestState.RESTORING
        self._restoring.append((req, ticket))
        return True

    def _apply_prefetch_hint(self, key: np.ndarray) -> None:
        """Start a no-request restore for a routed-ahead prefix. Hints
        are strictly weaker than admissions: read-only match (no node
        splits), allocation straight from the free list (never evicts),
        joined with any in-flight restore of the same nodes — so a
        duplicate, stale, or raced hint degrades to a no-op."""
        plane = self.kv_transfer
        if plane is None or not hasattr(self.tree, "match_and_load"):
            return
        if self.draining:
            # Drain races a router hint: the router stops hinting once
            # the DRAINING state gossips, but frames already in flight
            # land here — drop them (counted) instead of opening a
            # restore ticket nothing will ever be routed here to use.
            plane.count_hint("draining")
            return
        match = self.tree.match_prefix(key, split_partial=False)
        if not match.host_nodes and not match.disk_nodes:
            plane.count_hint("noop")
            return
        ticket = plane.begin_restore(
            self.tree, match, alloc=self.pool.alloc, auto_release=True
        )
        plane.count_hint("started" if ticket is not None else "noop")

    def _defer_for_prefix_wave(
        self, req: Request, cached: int, group: list[tuple]
    ) -> bool:
        """True if ``req`` shares ≥1 page of NOT-yet-cached prefix (beyond
        its ``cached`` match length) with a request already collected this
        wave — or parked in the inline backlog (mixed waves): either one
        will publish that span, so waiting turns recomputation into a
        hit."""
        peers = [g[0] for g in group] + [j.req for j in self._inline]
        if not peers:
            return False
        prompt = req.prompt
        span = cached - cached % self.page_size + self.page_size
        if len(prompt) < span:
            return False
        head = prompt[:span]
        return any(
            len(p.prompt) >= span and np.array_equal(p.prompt[:span], head)
            for p in peers
        )

    def _acquire_prompt_slots(
        self, req: Request, match=None
    ) -> tuple[int, np.ndarray, np.ndarray] | None:
        """Lock the longest cached prefix of ``req.prompt`` and allocate
        pages for the remainder. Returns ``(reuse, prefix_slots, own)``, or
        ``None`` after full rollback if the pool can't satisfy it. Reuse is
        page-aligned and always leaves ≥1 token uncached so prefill has
        logits to sample the first output token from. ``match`` may carry a
        just-computed match result to avoid a second tree walk."""
        prompt = req.prompt
        if match is None:
            # Hierarchical trees restore host-resident extensions into
            # device slots as part of the match (host→HBM copy beats a
            # recompute).
            if hasattr(self.tree, "match_and_load"):
                match = self.tree.match_and_load(prompt)
            else:
                match = self.tree.match_prefix(prompt)
        reuse = min(
            match.length, (len(prompt) - 1) // self.page_size * self.page_size
        )
        matched = reuse
        if self._resumes:
            # The rows alone serve no prefix: the request resumes at the
            # deepest snapshot at or before them, and computes from there.
            reuse, resume = match.state_at_or_before(matched)
            if resume and self._ordered_evict:
                # Pinned before anything below can evict it: there a lock
                # does not keep a snapshot (``_evict_states``).
                self._resuming[resume] += 1
        prefix_slots = match.indices()[:reuse]
        self.tree.inc_lock_ref(match.last_node)
        req.lock_node, req.lock_end = match.last_node, match.length
        own = self._alloc_pages(-(-(len(prompt) - reuse) // self.page_size))
        if own is not None and self.state_stats is not None:
            with self.spans.span("rm.state.restore"):
                if not self._acquire_states(req, match, matched, reuse, resume):
                    self.pool.free(own)
                    own = None
                elif self.window_stats is not None and not self._acquire_window(
                        req, match.tail_at(reuse), reuse):
                    self._free_states(req)
                    resume = None  # unpinned with the states
                    self.pool.free(own)
                    own = None
        elif own is not None and self.tail_stats is not None:
            # Nothing between the match and here evicts a tail, and the
            # counts below are taken before anything after can.
            with self.spans.span("rm.state.restore"):
                if self._acquire_window(req, match.tail_at(reuse), reuse):
                    self._plan_tails(req, match, matched, reuse)
                else:
                    self.pool.free(own)
                    own = None
        if own is None:
            if self._ordered_evict and resume and not req.state_resume:
                self._unpin(resume)
            self.tree.dec_lock_ref(req.lock_node)
            req.lock_node = None
            return None
        return reuse, prefix_slots, own

    # ------------------------------------------------------------------
    # state slots (a model whose layers carry a state beside the rows)
    # ------------------------------------------------------------------

    def _alloc_state(self) -> int:
        """A state slot, evicting the tree's least recently used
        snapshots when none is free (the rows stay); 0: none to be had."""
        slot = self.pool.alloc_state()
        if slot is None and self._evict_states(_STATE_EVICT_BATCH):
            slot = self.pool.alloc_state()
        return slot or 0

    def _evict_states(self, n: int) -> int:
        """Pressure on the snapshots. With a window group a snapshot is 23
        MiB, with kda layers 12 MiB, not 240 KiB, and a session's path
        holds one or two a turn under its lock: there the locked ones may
        go too, superseded ones first, all but those a request is about to
        resume from (``RadixTree.evict_states``)."""
        if not self._ordered_evict:
            return self.tree.evict_states(n)
        # ... nor a live row's own prompt snapshot before a decode step of
        # the row has completed a page: until then its release leaves no
        # deeper one (at a page of 128 a reply of 64 tokens often never
        # does), and the session's next turn would compute its prefix again.
        own = {slot for req in self._rows
               if req is not None and not req.state_boundary_len
               for slot in req.state_handed}
        return self.tree.evict_states(min(n, _TAIL_EVICT_BATCH),
                                      pinned=own.union(self._resuming))

    # ------------------------------------------------------------------
    # the window group (a model whose window layers keep rows of their own)
    # ------------------------------------------------------------------

    def _alloc_window(self, n_pages: int) -> np.ndarray | None:
        """Pages of the window group, evicting the tree's snapshots (state
        and tail) while that frees any; None: none to be had."""
        pages = self.pool.alloc_window(n_pages)
        while pages is None and self._evict_states(_TAIL_EVICT_BATCH):
            pages = self.pool.alloc_window(n_pages)
        return pages

    def _tail_freed(self, tail: np.ndarray) -> None:
        """The tree gave up a snapshot's window tail."""
        self.pool.unref_window(tail)
        self.window_stats["tail_slots_in_tree"] -= len(tail) * self.page_size

    def _acquire_window(self, req: Request, tail, reuse: int) -> bool:
        """The window pages a request's prefill needs: a count on every
        page of the tail of the snapshot it resumes from (read-only: they
        hold the window before ``reuse``) and pages of its own from
        ``reuse`` to the prompt's end. False, nothing held, if the group
        has none to give."""
        ps = self.page_size
        tail = np.empty(0, np.int32) if tail is None else tail
        self.pool.ref_window(tail)  # before any eviction could free them
        own = self._alloc_window(-(-(len(req.prompt) - reuse) // ps))
        if own is None:
            self.pool.unref_window(tail)
            return False
        req.w_first = reuse // ps - len(tail)
        req.w_pages = [*tail.tolist(), *own.tolist()]
        return True

    def _unpin(self, slot: int) -> None:
        """One request less is about to resume from state slot ``slot``."""
        if slot and self._resuming[slot] > 0:
            self._resuming[slot] -= 1
            if not self._resuming[slot]:
                del self._resuming[slot]

    def _free_window(self, req: Request) -> None:
        if req.w_pages:
            self.pool.unref_window(req.w_pages)
        req.w_first, req.w_pages = 0, []

    def _release_window(self, req: Request, length: int) -> None:
        """Give back the pages that have left the window of a row that
        holds ``length`` tokens: those wholly before the ``sliding_window``
        tokens that end with the last page boundary at or before
        ``length`` (what the next positions attend, and what a snapshot
        cut there takes as its tail) — and not before the tail of a
        snapshot the row has yet to hand over."""
        ps = self.page_size
        pending = [pos for pos, _ in req.state_snaps]
        keep = (min([length, *pending]) // ps) - self.cfg.sliding_window // ps
        n = min(keep - req.w_first, len(req.w_pages))
        if n <= 0:
            return
        with self.spans.span("rm.window.release"):
            gone, req.w_pages = req.w_pages[:n], req.w_pages[n:]
            if req.row >= 0:
                self._w_page_table_padded[
                    req.row, req.w_first:req.w_first + n] = 0
            req.w_first += n
            self.pool.unref_window(gone)
            self.window_stats["pages_released"] += n

    def _acquire_states(
        self, req: Request, match, matched: int, reuse: int, resume: int | None
    ) -> bool:
        """The slots a request's life needs — its working slot and its
        boundary slot (False, nothing held, if the pool has neither) — and
        one for each snapshot its prefill will leave for the tree: (a) at
        the page-aligned cut of its prompt, where ``_publish`` will cut
        the key, and (b) at ``matched``, what the tree's rows would have
        served, when it resumes below that — the second visitor of a
        branch point pays the recompute, every later one resumes there. A
        snapshot the pool has no slot for is skipped: a later match
        resumes shallower."""
        work = self._alloc_state()
        keep = self._alloc_state() if work else 0
        if not keep:
            if work:
                self.pool.free_state(work)
            return False
        req.state_slot, req.state_boundary = work, keep
        req.state_boundary_len = 0
        req.state_resume = resume or 0
        req.state_snaps = []
        for pos in self._snapshot_cuts(req, match, matched, reuse, self.state_stats):
            slot = self._alloc_state()
            if slot:
                req.state_snaps.append((pos, slot))
            else:
                self.state_stats["snapshots_skipped"] += 1
        return True

    def _snapshot_cuts(self, req: Request, match, matched: int, reuse: int,
                       st: dict) -> list[int]:
        """Where ``req``'s prefill will leave a resume point for the tree —
        its prompt's page-aligned cut and, when it resumes below it, its
        match — unless one lies there already; and the three counters of
        how it resumed, into ``st``."""
        st["kv_matched_tokens"] += matched
        st["resumed_tokens"] += reuse
        st["recomputed_tokens"] += matched - reuse
        have = {pos for pos, _ in match.states}
        ps = self.page_size
        return [pos for pos in sorted({len(req.prompt) // ps * ps, matched})
                if pos > reuse and pos not in have]

    def _plan_tails(self, req: Request, match, matched: int, reuse: int) -> None:
        """``_acquire_states`` for a model whose resume points are tails
        alone: the same two places, and nothing to allocate (a tail is
        counts on pages the row holds anyway: slot 0 stands for "none")."""
        st = self.tail_stats
        req.state_snaps = [
            (pos, 0) for pos in self._snapshot_cuts(req, match, matched, reuse, st)]
        st["matches"] += matched > 0
        st["resumes"] += reuse > 0

    def _free_states(self, req: Request) -> None:
        """Return every state slot ``req`` still holds."""
        for slot in (req.state_slot, req.state_boundary,
                     *(s for _, s in req.state_snaps)):
            if slot:
                self.pool.free_state(slot)
        self._unpin(req.state_resume)
        req.state_slot = req.state_boundary = req.state_resume = 0
        req.state_boundary_len = 0
        req.state_snaps, req.state_handed = [], []
        if self.window_stats is not None:
            self._free_window(req)

    def _hand_over_states(self, req: Request) -> None:
        """Give the tree every snapshot of ``req`` that lies on the key
        just published, which ``req.lock_node`` ends (a node takes it, or
        has one and declines)."""
        keep = []
        for pos, slot in req.state_snaps:
            if pos > req.lock_end:
                keep.append((pos, slot))
                continue
            tail = None if self.window_stats is None else self._tail_of(req, pos)
            st = self.state_stats or self.tail_stats
            if self.tree.attach_state_at(
                    req.lock_node, req.lock_end, pos, slot or None, tail):
                st["snapshots"] += 1
                if slot:
                    req.state_handed.append(slot)
                if tail is not None:
                    self.window_stats["tail_slots_in_tree"] += (
                        len(tail) * self.page_size)
            else:
                if slot:
                    self.pool.free_state(slot)
                if tail is not None:
                    self.pool.unref_window(tail)
                st["snapshots_declined"] += 1
        req.state_snaps = keep

    def _tail_of(self, req: Request, pos: int) -> np.ndarray:
        """The window pages of the ``sliding_window`` tokens before the
        page-aligned ``pos`` of ``req``'s sequence, counted once more: a
        snapshot's tail, for the tree to hold."""
        ps = self.page_size
        end = pos // ps - req.w_first
        first = max(0, end - self.cfg.sliding_window // ps)
        tail = np.asarray(req.w_pages[first:end], np.int32)
        self.pool.ref_window(tail)
        return tail

    def _chunk_states(self, reqs, starts, nv, B: int, first):
        """``(state_in [B], snap_at [B, 3], state_out [B, 3])`` of one
        launch of a prefill group, whose batch row ``j`` computes ``nv[j]``
        tokens of ``reqs[j]`` from position ``starts[j]``: the row resumes
        from its working slot (in its ``first`` launch: from the tree's
        snapshot) and leaves its state at the launch's true end there,
        and wherever one of its snapshots falls inside the launch, in
        that snapshot's slot. What is not wanted goes to slot 0."""
        state_in = np.zeros((B,), np.int32)
        snap_at = np.zeros((B, _STATE_SNAPS), np.int32)
        state_out = np.zeros((B, _STATE_SNAPS), np.int32)
        for j, req in enumerate(reqs):
            state_in[j] = req.state_resume if first[j] else req.state_slot
            snap_at[j, 0], state_out[j, 0] = nv[j], req.state_slot
            inside = [(pos - starts[j], slot) for pos, slot in req.state_snaps
                      if starts[j] < pos <= starts[j] + nv[j]]
            for k, (at, slot) in enumerate(inside, start=1):
                snap_at[j, k], state_out[j, k] = at, slot
        return {"state_in": jnp.asarray(state_in),
                "snap_at": jnp.asarray(snap_at),
                "state_out": jnp.asarray(state_out)}

    def _install_running(self, req: Request, row: int, reuse: int) -> None:
        """Shared tail of admission when the first token is ALREADY known
        (disaggregated handoff): install + record TTFT in one go."""
        self._install_prefilled(req, row, reuse)
        self._record_first_token(req)

    def _install_prefilled(
        self, req: Request, row: int, reuse: int, inline: bool = False
    ) -> None:
        """Mark RUNNING, record stats, publish the prompt
        (``cache_unfinished_req``, ``radix_cache.py:488-519``), and wire the
        decode row. ``req.kv_len``/``token_slots``/``own_slots`` must be
        set. The first token MAY still be in flight on device (collocated
        admission defers the sample sync until every wave of the admission
        round has been dispatched — one device→host round trip total);
        ``_finalize_first_tokens`` fills it in before decode runs."""
        req.prefix_len = req.canon_len = reuse
        req.state = RequestState.RUNNING
        req.row = row
        # Room for the reply: a decode step appends its slot in place.
        req.reserve_slots(min(req.sampling.max_new_tokens,
                              self.max_seq_len - req.kv_len))

        self.stats.prefills += 1
        # Stall attribution (obs/token_timeline.py): a decode gap that
        # spans this instant is a prefill convoy, not a scheduler stall
        # — UNLESS the prefill was a budget-bounded inline chunk riding
        # the decode wave, which gets its own (non-convoy) cause so the
        # mitigation cannot masquerade as the disease it cures.
        if inline:
            self._last_inline_prefill_t = time.monotonic()
        else:
            self._last_prefill_t = time.monotonic()
        self.stats.prompt_tokens += len(req.prompt)
        self.stats.cached_tokens += reuse
        self._m_prompt.inc(len(req.prompt))
        self._m_cached.inc(reuse)
        self._m_hit_len.observe(reuse)
        if req.resume_offset:
            # Resurrection hit accounting: the whole resumed prompt
            # (original prompt + delivered tokens) is replay; the cache
            # served ``reuse`` of it. The chaos gate pins the fleet-wide
            # ratio ≥ 0.8 — replay must be a hit, not a recompute.
            self.stats.replayed_cached_tokens += reuse

        self._publish(req, len(req.prompt))
        if self.window_stats is not None:
            # The snapshots are the tree's now: what left the window goes
            # back (a prefill holds its whole new span until here).
            self._release_window(req, req.kv_len)
            self._w_page_table_padded[row] = 0
            self._w_page_table_padded[
                row, req.w_first:req.w_first + len(req.w_pages)] = req.w_pages

        self._rows[row] = req
        if req.output_tokens:
            self._tokens[row] = req.output_tokens[-1]
        self._temps[row] = req.sampling.temperature
        self._top_ps[row] = req.sampling.top_p
        self._top_ks[row] = req.sampling.top_k
        self._page_table[row] = self._scratch_page
        n_pages = -(-req.kv_len // self.page_size)
        self._page_table[row, :n_pages] = (
            req.token_slots[:: self.page_size] // self.page_size
        )
        self._groups_for = None  # a request that comes back has other pages

    @staticmethod
    def _seeded_launch(rows: Iterable[Request]) -> bool:
        """True when EVERY row is seeded — the replay-determinism
        contract's scope. A mixed batch samples from the global stream
        (documented best-effort): determinism is a per-launch contract,
        never a cross-request entanglement."""
        rows = [r for r in rows if r is not None]
        return bool(rows) and all(
            r.sampling.seed is not None for r in rows
        )

    def _launch_draws(self, rows: Iterable[Request]) -> bool:
        """THE rule for which sampler executable a launch runs
        (``ops/sampling.py``, static ``draws``): False — the argmax and
        nothing else — when no row of the launch draws, by the sampler's
        own test (``temperature <= 0`` is greedy). ``rows`` are the
        requests whose tokens the launch will hand out, never
        ``self._temps``: a released row keeps its last temperature there
        until the next request is installed, and one finished sampling
        request must not pin the sort on an empty row. Counted per
        launch in ``self.sampler`` (/debug/state)."""
        draws = any(not (r.sampling.temperature <= 0.0) for r in rows)
        self.sampler[
            "drawing_launches" if draws else "argmax_launches"
        ] += 1
        return draws

    def _seed_key(self, req: Request) -> jax.Array:
        """Canonical per-row sampling key: a pure function of (seed,
        absolute token position). ``req.num_tokens`` IS the position of
        the token about to be drawn — and for a resumed request
        (``resume_offset``) the delivered tokens ride in the prompt, so
        positions line up exactly across lives."""
        # Mix the seed BEFORE combining with the position: a shift-then-
        # mask would throw away the seed's top bits, silently colliding
        # distinct user-supplied seeds.
        acc = _mix64(_mix64(int(req.sampling.seed) & _M64) ^ req.num_tokens)
        # A raw uint32[2] array IS a legacy threefry key — no jax
        # dispatch on the host path to build it.
        return jnp.asarray(
            np.array(
                [(acc >> 32) & 0xFFFFFFFF, acc & 0xFFFFFFFF],
                dtype=np.uint32,
            )
        )

    def _sample_seeded_row(self, req: Request, logit_row) -> int:
        """THE canonical seeded draw: one [1, V] ``sample_tokens`` call
        keyed by (seed, position). Every seeded sampling site — first
        token after prefill, every decode step, on any node — uses this
        exact shape and key schedule, so a request resurrected on
        another node redraws the same continuation its first life would
        have drawn (the categorical draw depends on the batch SHAPE, so
        shape-stability here is what makes cross-life replay exact). A
        greedy row's token is the argmax in either life: no key."""
        if self._launch_draws((req,)):
            tok = sample_tokens(
                logit_row[None, :],
                self._seed_key(req),
                temperature=jnp.asarray(
                    [req.sampling.temperature], jnp.float32
                ),
                top_p=jnp.asarray([req.sampling.top_p], jnp.float32),
                top_k=jnp.asarray([req.sampling.top_k], jnp.int32),
            )
        else:
            tok = sample_tokens(logit_row[None, :], None, draws=False)
        return int(np.asarray(tok)[0])

    def _record_first_token(self, req: Request) -> None:
        self.stats.ttft_s.append(req.first_token_time - req.submit_time)
        self._m_ttft.observe(req.first_token_time - req.submit_time)
        self._m_req_ttft.labels(tenant=req.tenant).observe(
            req.first_token_time - req.submit_time,
            trace_id=getattr(req.trace, "trace_id", None),
        )
        tr = req.trace
        if tr is not None:
            tr.add(
                "first_token", req.first_token_time, 0.0, cat="scheduler",
                ttft_s=round(req.first_token_time - req.submit_time, 6),
            )
        if self.on_first_token is not None:
            self.on_first_token(req)

    def _finalize_first_tokens(self, pending: list[tuple]) -> None:
        """ONE batched sample + ONE device→host copy for every request
        admitted this round (per-request syncs made TTFT scale with queue
        depth)."""
        if self._seeded_launch(r for r, _ in pending):
            # Seeded replay: each row draws through the canonical
            # shape-stable (seed, position) path instead of the batched
            # sample — a resumed request's first token is exactly the
            # token its first life drew at that position.
            now = time.monotonic()
            for req, logit in pending:
                self._keep_logits(req, logit)
                tok = self._sample_seeded_row(req, logit)
                req.first_token_time = now
                req.output_tokens = [tok]
                self._tokens[req.row] = tok
                self._record_first_token(req)
                req.note_progress()
            return
        # Pad to a power-of-two batch (repeating row 0) so serving queue
        # depths don't each compile a fresh sample_tokens variant.
        n = len(pending)
        n_b = _pow2_at_least(n, floor=1)
        for req, logit in pending:
            self._keep_logits(req, logit)
        logits = [logit for _, logit in pending]
        pad = n_b - n
        logits_d = jnp.stack(logits + [logits[0]] * pad)
        if self._launch_draws(r for r, _ in pending):
            self._rng, key = jax.random.split(self._rng)
            temps = [r.sampling.temperature for r, _ in pending]
            tops = [r.sampling.top_p for r, _ in pending]
            topks = [r.sampling.top_k for r, _ in pending]
            sampled_d = sample_tokens(
                logits_d,
                key,
                temperature=jnp.asarray(temps + [0.0] * pad, jnp.float32),
                top_p=jnp.asarray(tops + [1.0] * pad, jnp.float32),
                top_k=jnp.asarray(topks + [0] * pad, jnp.int32),
            )
        else:
            sampled_d = sample_tokens(logits_d, None, draws=False)
        sampled = np.asarray(sampled_d)[:n]
        now = time.monotonic()
        for (req, _), tok in zip(pending, sampled):
            req.first_token_time = now
            req.output_tokens = [int(tok)]
            self._tokens[req.row] = int(tok)
            # The ITL clock starts HERE: the first token's latency is
            # TTFT, so the timeline's first gap is token 1 → token 2 —
            # but the first token is still useful output.
            if self.goodput is not None:
                req.last_token_time = now
                self.goodput.note_token(req.tenant)
            self._record_first_token(req)
            # Wake streamers parked on the request condition: this is
            # THE first-token site, and the next decode token's notify
            # may be a whole decode wave away.
            req.note_progress()

    @staticmethod
    def _keep_logits(req: Request, logit_row) -> None:
        """The logits hook: a request that asked (``return_logits``)
        keeps the float32 row the sampler is about to see. One
        device-to-host copy, for such a request only."""
        if req.sampling.return_logits:
            req.logit_rows.append(np.asarray(logit_row, dtype=np.float32))

    def _keep_expert_ids(self, req: Request, start: int, b: int, n: int) -> None:
        """Same hook, expert choices: positions ``start .. start + n`` of
        ``req`` were batch row ``b`` of the last launch."""
        if req.sampling.return_logits and self._last_expert_ids is not None:
            # One whole copy a launch, sliced on the host: an eager device
            # slice would compile a program for every (row, length).
            if not isinstance(self._last_expert_ids, np.ndarray):
                self._last_expert_ids = np.asarray(self._last_expert_ids)
            ids = self._last_expert_ids[:, b, :n]  # [L, n, k]
            req.expert_rows.append((int(start), ids.transpose(1, 0, 2).copy()))

    def _keep_selected(self, req: Request, position: int, b: int) -> None:
        """Same hook, learned sparse attention: what the full layers
        selected for the query at ``position`` of ``req``, batch row ``b``
        of the last launch (nothing where that launch attended all)."""
        if req.sampling.return_logits and self._last_selected is not None:
            if not isinstance(self._last_selected, np.ndarray):
                self._last_selected = np.asarray(self._last_selected)
            req.index_rows.append(
                (int(position), self._last_selected[:, b, 0].copy()))

    def _moe_kw(self) -> dict:
        """What a step of a model with experts takes beside the rest."""
        return {} if self._moe_stats is None else {"moe_stats": self._moe_stats}

    def _pool_kw(self) -> dict:
        """What a step that writes the pool takes beside ``pool.kv``: the
        counters of a model with experts, the index plane of one with
        learned sparse attention."""
        kw = self._moe_kw()
        if self.pool.index is not None:
            kw["index_pool"] = self.pool.index
        if self.pool.window is not None:
            kw["window_pool"] = self.pool.window
            if self.pool.state is not None:
                kw["state_pool"] = (self.pool.state, self.pool.state_conv)
        elif self.pool.state_conv is not None:
            kw["state_pool"] = (self.pool.state, self.pool.state_conv)
        elif self.pool.state is not None:
            kw["state_pool"] = self.pool.state
        return kw

    def _note_matrix(self, read: int = 0, written: int = 0, **counts) -> None:
        """``matrix_stats`` for one launch that read and wrote so many whole
        snapshots."""
        st = self.matrix_stats
        for name, n in counts.items():
            st[name] += n
        st["state_bytes_read"] += read * self.pool.state_slot_bytes
        st["state_bytes_written"] += written * self.pool.state_slot_bytes

    def _note_sparse(self, site: str, ctx: np.ndarray, engaged: bool) -> None:
        """``attn.sparse[site]`` for queries that have ``ctx`` rows each to
        attend (position + 1): with the selection ``engaged`` (the
        launch's context passes ``index_topk``) a query scores them all in
        the full layers and attends ``min(ctx, index_topk)``."""
        cfg = self.cfg
        held = int(ctx.sum())
        selected = int(np.minimum(ctx, cfg.index_topk).sum()) if engaged else held
        c = self.attn_window["sparse"][site]
        c["rows_held"] += held * cfg.n_layers
        c["rows_selected"] += selected * cfg.n_layers
        if engaged:
            c["index_keys_scored"] += held * len(cfg.full_layers)

    @property
    def prefill_width(self) -> int:
        """The widest chunk of a prefill launch: the power of two that
        holds the floor of ``prefill_cost`` where the chunk kernels admit
        it (``prefill_plan.chunk_width``), within the wave's tokens as
        they are now (the SLO ladder narrows a wave), never under
        ``prefill_chunk``. What ``subwaves`` slices by and
        ``plan_prefill`` launches at."""
        width = self._chunk_width
        while width > max(self.prefill_chunk, self.prefill_wave_tokens):
            width //= 2
        return width

    def _chunk_admits(self, width: int) -> bool:
        """Whether this model's chunk kernels take a chunk ``width`` wide,
        from shapes alone (``prefill_plan.chunk_width``). The attention
        kernel holds the chunk's own rows whole in VMEM and says; the
        selecting, delta-rule, SSD and convolution kernels walk a chunk in
        blocks of their own and take any."""
        cfg = self.cfg
        if cfg.latent:
            from radixmesh_tpu.ops.mla_attention import chunk_admits

            return chunk_admits(
                width, cfg.n_heads, cfg.kv_row[2], cfg.kv_lora_rank,
                self.pool.kv.dtype.itemsize)
        from radixmesh_tpu.ops.paged_attention import chunk_admits

        return chunk_admits(width, max_pages=self.max_pages, **self._chunk_shape)

    def _prefill_dense(self, req: Request, row: int, own: np.ndarray) -> tuple:
        """Dense prefill of one request with nothing cached: the whole
        prompt in one ``prefill_forward`` launch of its power-of-two
        bucket, no page table. (A request with a cached prefix is a group
        of one: :meth:`_prefill_group`.) ``_admit`` takes it where that one
        launch is bound by the weights it streams (``pow2(n) <=
        prefill_cost[0]``, up to ``long_prefill_threshold``): there no
        plan of chunk launches can cost less than the one launch, and the
        plainer attention shows nowhere — a model with experts, a short
        prompt. Above that the chunk launches are faster, as measured on
        a v5e at Qwen2-7B: 23.3 / 42.1 / 84.2 ms for 256 / 512 / 1 024
        tokens against 24.5 / 44.5 / 90.8 here, and a 544-token prompt is
        512 + 32, not 1 024 (PERF.md section 6, PR 47).
        Returns ``(req, final-logit device slice)`` for
        :meth:`_finalize_first_tokens`."""
        prompt = req.prompt
        n_new = len(prompt)
        s_b = _pow2_at_least(n_new)
        self._wave_shapes.append((1, s_b))
        tokens = np.zeros((1, s_b), dtype=np.int32)
        tokens[0, :n_new] = prompt
        positions = np.arange(s_b, dtype=np.int32)[None]
        _, heads, width = self.cfg.kv_row
        # No cached rows: an empty buffer stands for both planes.
        no_prefix = jnp.zeros(
            (len(self.cfg.attn_layers), 1, 0, heads, width),
            dtype=self.cfg.dtype)
        moe_kw = self._moe_kw()
        if moe_kw:  # keep the bucket's padding out of the counters
            moe_kw["new_lengths"] = jnp.full((1,), n_new, dtype=jnp.int32)
        if self.state_stats is not None:
            # The row's state at its true end, and at each snapshot.
            wanted = [(n_new, req.state_slot), *req.state_snaps]
            wanted += [(0, 0)] * (_STATE_SNAPS - len(wanted))
            moe_kw["snap_at"] = jnp.asarray([[at for at, _ in wanted]], jnp.int32)
        windowed = self.window_stats is not None
        # A cross-decoder computes the full layer's last position alone.
        last_only = self.cfg.cross_decoder
        if last_only:
            moe_kw["new_lengths"] = jnp.full((1,), n_new, dtype=jnp.int32)
        logits, new_k, new_v, *aux = self._launch(
            prefill_forward,
            self.params,
            self.cfg,
            jnp.asarray(tokens),
            jnp.asarray(positions),
            no_prefix,
            no_prefix,
            jnp.zeros((1,), dtype=jnp.int32),
            **moe_kw,
        )
        if self.cfg.dsa:  # the tokens' index keys, to the same slots
            index_keys, *aux = aux
            self.pool.write_index(own[:n_new], index_keys[:, 0, :n_new])
            self._note_sparse(
                "prefill", 1 + np.arange(n_new, dtype=np.int64),
                s_b > self.cfg.index_topk)
        if windowed:  # the window layers' rows, to the window group
            win_k, win_v, *aux = aux
            at = np.arange(n_new)
            w_slots = (np.asarray(req.w_pages, np.int32)[at // self.page_size]
                       * self.page_size + at % self.page_size)
            self.pool.write_window(w_slots, win_k[:, 0, :n_new], win_v[:, 0, :n_new])
            self.window_stats["slots_written"] += n_new
        if self.matrix_stats is not None:
            self._note_matrix(chunk_tokens=n_new, written=_STATE_SNAPS)
        if self.state_stats is not None:
            states, *aux = aux
            self.pool.write_state(
                np.asarray([slot for _, slot in wanted], np.int32),
                jax.tree_util.tree_map(lambda a: a[:, 0], states))
        if aux:
            self._moe_stats = aux[0]["moe_stats"]
            self._last_expert_ids = aux[0]["expert_ids"]
            self._last_selected = None  # the dense path selects as a mask
            self._keep_expert_ids(req, 0, 0, n_new)
        self.pool.write(
            own[:n_new], new_k[:, 0, :n_new],
            None if new_v is None else new_v[:, 0, :n_new],
        )

        req.output_tokens = []
        req.kv_len = n_new
        req.token_slots = own[:n_new].copy()
        req.own_slots = own
        self._install_prefilled(req, row, 0)
        return (req, logits[0, 0 if last_only else n_new - 1])

    def _pp_n_micro(self, batch: int) -> int:
        """GPipe microbatch count for a pp launch: fill every stage when
        the batch divides, otherwise fall back to one wave (correct, just
        bubble-bound — batches are pow2-padded so pp=2/4 always divides)."""
        pp = self.device_mesh.shape["pp"]
        return pp if batch % pp == 0 else 1

    def _forward_chunk(
        self, toks, poss, sl, pt, kvlen, kv_block: int, logits_at=None,
        states: dict | None = None,
    ):
        """One chunk forward through the right backend: the pipeline
        schedule under pp, ``prefill_chunk_paged`` otherwise. Shared by
        group prefill and the speculative verify pass so the dispatch
        cannot drift between them; quantized pools thread their scales
        through either path. ``logits_at`` ([B] chunk offsets) narrows the
        logits to ``[B, 1, V]`` — inside the jit where the launch
        supports it, so the all-position tensor is never materialized."""
        if self._pp:
            from radixmesh_tpu.parallel.pp_serving import pp_forward_chunk

            res = pp_forward_chunk(
                self.params,
                self.cfg,
                toks,
                poss,
                self.pool.kv,
                sl,
                pt,
                kvlen,
                page_size=self.page_size,
                kv_block_pages=kv_block,
                mesh=self.device_mesh,
                n_micro=self._pp_n_micro(toks.shape[0]),
                kv_scale=self.pool.kv_scale,
            )
            if logits_at is None:
                return res
            picked = jnp.take_along_axis(
                res[0], logits_at[:, None, None], axis=1
            )
            return (picked, *res[1:])
        return self._launch(
            prefill_chunk_paged,
            self.params,
            self.cfg,
            toks,
            poss,
            self.pool.kv,
            sl,
            pt,
            kvlen,
            page_size=self.page_size,
            kv_block_pages=kv_block,
            kv_scale=self.pool.kv_scale,
            mesh=self.device_mesh,
            logits_at=logits_at,
            **self._pool_kw(),
            **(states or {}),
        )

    def _sp_capable(self, member: tuple) -> bool:
        """A fresh (no cached prefix) long prompt on a mesh with an sp
        axis prefills sequence-sharded — ring attention over ICI."""
        req, _, reuse, *_ = member
        return (
            self.device_mesh is not None
            and self.device_mesh.shape.get("sp", 1) > 1
            and reuse == 0
            and len(req.prompt) >= self.sp_prefill_threshold
        )

    def _prefill_sp(
        self,
        req: Request,
        row: int,
        reuse: int,
        prefix_slots: np.ndarray,
        own: np.ndarray,
    ) -> tuple:
        """Sequence-parallel prefill of one fresh prompt: the whole span in
        ONE sharded call (``prefill_forward_sp``), sequence split over the
        sp mesh axis, ring attention over ICI. KV lands in the paged pool
        via a sharded scatter."""
        from radixmesh_tpu.models.llama import prefill_forward_sp

        prompt = req.prompt
        n = len(prompt)
        sp = self.device_mesh.shape["sp"]
        s_b = _pow2_at_least(n, floor=max(16, sp))
        s_b = -(-s_b // sp) * sp  # shard_map needs S divisible by sp
        self._wave_shapes.append((1, s_b))
        tokens = np.zeros((1, s_b), dtype=np.int32)
        tokens[0, :n] = prompt
        positions = np.arange(s_b, dtype=np.int32)[None]
        logits, new_k, new_v = prefill_forward_sp(
            self.params,
            self.cfg,
            jnp.asarray(tokens),
            jnp.asarray(positions),
            self.device_mesh,
            logits_at=jnp.asarray([n - 1], dtype=jnp.int32),
        )
        self.pool.write(own[:n], new_k[:, 0, :n], new_v[:, 0, :n])
        req.output_tokens = []
        req.kv_len = n
        req.token_slots = own[:n].copy()
        req.own_slots = own
        self._install_prefilled(req, row, reuse)
        return (req, logits[0, 0])

    def _prefill_group(self, group: list[tuple]) -> list[tuple]:
        """Chunked-paged prefill of a sub-wave: ``group`` of acquired
        requests — one request with a cached prefix is a group of one —
        through the launches :func:`plan_prefill` draws for their lengths
        (engine/prefill_plan.py: the rows still live, seated in launches
        of 2^k rows, a short tail in launches of its own), each a
        ``prefill_chunk_paged`` call writing K/V into the pool in place
        and attending blockwise via the page table — a cached prefix is
        read where it lies, peak memory O(batch · chunk · block)
        regardless of prompt length. Ragged offsets are exact: every row
        of a launch carries its own positions and kv-length, whatever its
        launch-mates'; padded rows ride the scratch slot. One batched
        sample at the end → one host sync for the whole group.

        The host side is one pass over the rows a wave (their new tokens,
        slots and pages); a launch's inputs are slices of that, and a
        page table is uploaded once for the launches that share it."""
        ps = self.page_size
        kv_block = _KV_BLOCK_PAGES
        reuses = np.array([g[2] for g in group], dtype=np.int64)
        totals = np.array([len(g[0].prompt) for g in group], dtype=np.int64)
        new = totals - reuses
        floor_tokens, launch_tokens = self.prefill_cost
        plan = plan_prefill(
            list(zip(new.tolist(), reuses.tolist())),
            chunk=self.prefill_chunk, page_size=ps, max_pages=self.max_pages,
            floor_tokens=floor_tokens, launch_tokens=launch_tokens,
            width_selects=self.cfg.dsa, one_width=self._resumes,
            width=self.prefill_width,
        )
        windowed = self.window_stats is not None
        token_slots_all, pages_all, w_pages_all, w_slots_all = [], [], [], []
        for i, (req, _, reuse, prefix_slots, own) in enumerate(group):
            ts = np.concatenate([prefix_slots, own[: new[i]]])
            token_slots_all.append(ts)
            pages_all.append(ts[::ps] // ps)
            if windowed:  # the row's pages and slots in the window group
                held = req.w_first + len(req.w_pages)
                wp = np.zeros((max(len(pages_all[i]), held),), dtype=np.int32)
                wp[req.w_first:held] = req.w_pages
                at = reuse + np.arange(new[i])
                w_pages_all.append(wp)
                w_slots_all.append(wp[at // ps] * ps + at % ps)
        if windowed:
            self.window_stats["slots_written"] += int(new.sum())
        keeps = any(g[0].sampling.return_logits for g in group)
        tables: dict = {}  # (rows, B, width) -> the uploaded page tables

        final_logits: list = [None] * len(group)
        for launch in plan:
            B, C, maxp = launch.B, launch.C, launch.pages
            idx = np.asarray(launch.rows)
            n = len(idx)
            done = np.asarray(launch.starts, dtype=np.int64)
            nv = np.asarray(launch.lens, dtype=np.int64)  # rows a run (kv.write)
            starts = reuses[idx] + done  # [n] tokens in the pool before
            self._wave_shapes.append((B, C))
            self.attn_window["table_pages_max"] = max(
                self.attn_window["table_pages_max"], maxp)
            toks = np.zeros((B, C), dtype=np.int32)
            sl = np.full((B, C), self._scratch_slot, dtype=np.int32)
            for j, i in enumerate(launch.rows):
                req, _, _, _, own = group[i]
                toks[j, : nv[j]] = req.prompt[starts[j] : starts[j] + nv[j]]
                sl[j, : nv[j]] = own[done[j] : done[j] + nv[j]]
            if (launch.rows, B, maxp) not in tables:
                pt = np.full((B, maxp), self._scratch_page, dtype=np.int32)
                wt = np.zeros((B, maxp), dtype=np.int32)
                for j, i in enumerate(launch.rows):
                    pt[j, : len(pages_all[i])] = pages_all[i]
                    if windowed:
                        wt[j, : len(w_pages_all[i])] = w_pages_all[i]
                tables[launch.rows, B, maxp] = (
                    jnp.asarray(pt), jnp.asarray(wt) if windowed else None)
            pt_dev, wt_dev = tables[launch.rows, B, maxp]
            offs = np.arange(C, dtype=np.int64)
            poss = np.zeros((B, C), dtype=np.int32)
            poss[:n] = np.minimum(starts[:, None] + offs, self.max_seq_len - 1)
            kvlen = np.zeros((B,), dtype=np.int32)
            kvlen[:n] = starts + nv
            # Only each row's LAST prompt position is ever sampled, and
            # only in the launch that holds it.
            last = starts + nv == totals[idx]
            at = np.zeros((B,), dtype=np.int32)
            at[:n] = np.where(last, nv - 1, 0)
            if self.cfg.latent:  # rows in pages that this launch attends
                self.attn_window["latent_prefix_tokens"] += (
                    int(starts.sum()) * len(self.cfg.attn_layers)
                )
            if self.cfg.dsa:
                self._note_sparse(
                    "prefill",
                    (starts[:, None] + offs + 1)[offs < nv[:, None]],
                    maxp * ps + C > self.cfg.index_topk,
                )
            self.pool.note_runs(sl[:n, 0], nv)
            if not self.cfg.latent:
                cblk = self._chunk_query_tokens(C, max_pages=maxp)
                blocks = B * (C // cblk)
                self.attn_window["chunk_query_blocks"] += blocks
                self.attn_window["chunk_query_blocks_skipped"] += (
                    blocks - int((-(-nv // cblk)).sum()))
            reqs = [group[i][0] for i in launch.rows]
            states = None
            if self.state_stats is not None:
                states = self._chunk_states(reqs, starts, nv, B, done == 0)
            if self.matrix_stats is not None:
                self._note_matrix(chunk_tokens=int(nv.sum()), read=B,
                               written=B * _STATE_SNAPS)
            if windowed:
                w_sl = np.zeros((B, C), dtype=np.int32)
                for j, i in enumerate(launch.rows):
                    w_sl[j, : nv[j]] = w_slots_all[i][done[j] : done[j] + nv[j]]
                states = (states or {}) | {
                    "window_slots": jnp.asarray(w_sl), "window_table": wt_dev}
            res = self._forward_chunk(
                jnp.asarray(toks),
                jnp.asarray(poss),
                jnp.asarray(sl),
                pt_dev,
                jnp.asarray(kvlen),
                kv_block,
                logits_at=jnp.asarray(at),
                states=states,
            )
            logits = self._commit_pool_update(res)
            if self._resuming:  # a row's first launch has read its snapshot
                for req, first in zip(reqs, done == 0):
                    if first:
                        self._unpin(req.state_resume)
                        req.state_resume = 0
            for j in np.flatnonzero(last).tolist():
                final_logits[idx[j]] = logits[j, 0]
            if keeps:  # the logits hook: expert ids, selected rows
                for j, req in enumerate(reqs):
                    self._keep_expert_ids(req, int(starts[j]), j, int(nv[j]))
                    if last[j]:
                        self._keep_selected(req, int(totals[idx[j]]) - 1, j)

        out = []
        for i, (req, row, reuse, prefix_slots, own) in enumerate(group):
            req.output_tokens = []
            req.kv_len = int(totals[i])
            req.token_slots = token_slots_all[i]
            req.own_slots = own
            self._install_prefilled(req, row, reuse)
            out.append((req, final_logits[i]))
        return out

    # ------------------------------------------------------------------
    # publish / release (the cache_*_req contract)
    # ------------------------------------------------------------------

    def _sequence_key(self, req: Request, key_len: int, start: int = 0) -> np.ndarray:
        """Tokens ``[start, key_len)`` of ``req``'s sequence (prompt, then
        output)."""
        n = len(req.prompt)
        if key_len <= n:
            return req.prompt[start:key_len]
        out = np.asarray(
            req.output_tokens[max(start - n, 0) : key_len - n], dtype=np.int32
        )
        return out if start >= n else np.concatenate([req.prompt[start:], out])

    def _publish(self, req: Request, key_len: int) -> None:
        """Insert the first ``key_len`` tokens (whose KV is in the pool)
        into the tree; canonicalize shared prefixes; move the lock to the
        deepest published node. The walk starts where ``req``'s lock ends
        (``RadixTree.insert_below``): the lock keeps everything above in
        place, so the publish builds, copies and compares only what is new
        since (``self.tree_publish`` counts both)."""
        with self.spans.span("rm.tree.publish"):
            tr = req.trace
            t_pub = time.monotonic() if tr is not None else 0.0
            ps = self.page_size
            aligned = key_len - key_len % ps
            node, base = req.lock_node, req.lock_end
            if node is None:
                node, base = self.tree.root, 0
            # Over [canon_len, base) — a first publish's recomputed span:
            # ``_acquire_prompt_slots`` resumes at or below its match — and
            # over what of the tail was already present, the tree kept its
            # slots. Where they're ours (this request published them
            # earlier) nothing changes; where another request published the
            # same tokens first, ours are duplicates — point our page table
            # at the canonical slots and free only the differing ones.
            lo = min(req.canon_len, base)
            if self.mesh is None:
                tail = self._sequence_key(req, aligned, base)
            else:  # the ring is told the whole key: only there is it built
                key = self._sequence_key(req, aligned)
                tail = key[base:]
            present, new_lock, canon = self.tree.insert_below(
                node, base, tail, req.token_slots[base:aligned].copy(),
                canon_from=lo,
            )
            self.tree_publish["key_tokens"] += key_len
            self.tree_publish["walked_tokens"] += (
                key_len - lo if self.mesh is None else key_len)
            matched = base + present
            if matched > lo:
                old = req.token_slots[lo:aligned]
                dup = old[old != canon]
                if dup.size:
                    old[:] = canon
                    req.own_slots = np.setdiff1d(req.own_slots, dup)
                    self.pool.free(dup)
            # Slots now referenced by tree nodes are tree-owned: drop them from
            # own_slots so release() never double-frees them.
            tree_owned = req.token_slots[matched:aligned]
            if tree_owned.size:
                req.own_slots = np.setdiff1d(req.own_slots, tree_owned)
            if new_lock is not req.lock_node:
                self.tree.inc_lock_ref(new_lock)
                if req.lock_node is not None:
                    self.tree.dec_lock_ref(req.lock_node)
                req.lock_node = new_lock
            req.lock_end = req.canon_len = aligned
            if req.state_snaps:
                with self.spans.span("rm.state.snapshot"):
                    self._hand_over_states(req)
            if self.mesh is not None and aligned > 0:
                # Advertise the (canonical) published prefix around the ring
                # (radix_mesh.py:193-201). Only the page-ALIGNED prefix: the
                # local tree truncates inserts to page multiples, so residue
                # slots [aligned, key_len) are freed at release — advertising
                # them would map tokens to recycled slots ring-wide, and the
                # router would promise hits the node cannot serve. A traced
                # request's trace id rides the frames (old-wire-tolerant
                # trailer) so replicas stitch their apply/lag spans under it.
                # A copy: the row keeps writing its buffer.
                self.mesh.insert(
                    key,
                    req.token_slots[:aligned].copy(),
                    trace_id=tr.trace_id if tr is not None else 0,
                )
            if tr is not None:
                tr.add(
                    "publish",
                    t_pub,
                    time.monotonic() - t_pub,
                    cat="cache",
                    tokens=int(key_len),
                    ring_advertised=bool(self.mesh is not None and aligned > 0),
                )

    def _release(self, req: Request) -> None:
        """cache_finished_req (radix_cache.py:439-486): publish the full
        sequence, free unpublished residue, release the lock, free the row."""
        if req.state_boundary_len:
            # The key is cut at the last page boundary: a decode step
            # completed that page and kept its state for exactly this.
            req.state_snaps.append((req.state_boundary_len, req.state_boundary))
            req.state_boundary = 0
        self._publish(req, req.kv_len)
        if self._resumes:
            self._free_states(req)
        if req.own_slots.size:
            self.pool.free(req.own_slots)
            req.own_slots = np.empty(0, dtype=np.int32)
        if req.lock_node is not None:
            self.tree.dec_lock_ref(req.lock_node)
            req.lock_node = None
        if req.row >= 0:
            self._rows[req.row] = None
            self._page_table[req.row] = self._scratch_page
            if self._w_page_table_padded is not None:
                self._w_page_table_padded[req.row] = 0
            self._tokens[req.row] = 0
            req.row = -1

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------

    def _commit_pool_update(self, res):
        """Unpack a model call's ``(out, kv_pool[, kv_scale][, aux])``
        result: store the updated pool buffers and return the leading
        value (the quantized pool threads its scale array through every
        call, a model with learned sparse attention its index plane; a
        model with experts returns its counters and the launch's expert
        ids as a trailing ``aux``)."""
        if self._moe_stats is not None:
            *res, aux = res
            self._moe_stats = aux["moe_stats"]
            self._last_expert_ids = aux["expert_ids"]
            self._last_selected = aux.get("index_positions")
        if self.pool.quant is not None:
            out, self.pool.kv, self.pool.kv_scale = res
        elif self.pool.index is not None:
            out, self.pool.kv, self.pool.index = res
        elif self.pool.window is not None:
            out, self.pool.kv, self.pool.window, *states = res
            if states:  # a model that carries a state beside both groups
                self.pool.state, self.pool.state_conv = states[0]
        elif self.pool.state_conv is not None:
            out, self.pool.kv, (self.pool.state, self.pool.state_conv) = res
        elif self.pool.state is not None:
            out, self.pool.kv, self.pool.state = res
        else:
            out, self.pool.kv = res
        return out

    def _decode_pt_bucket(
        self, active: list[tuple], headroom: int, floor: int = 4
    ) -> np.ndarray:
        """Length-bucketed page-table slice for one decode launch: wide
        enough for the context of every row of the launch (``active``)
        plus ``headroom`` tokens, bucketed to a power of two. A mixed
        batch must not pay the
        ``max_seq_len``-wide table on every step — short rows were
        attending (masked) over the full 8k-table width, which is THE
        wide-workload TTFT collapse (VERDICT round-3 weak #2 / next-step
        #6). Each bucket is one extra jit variant, bounded by log2(max
        pages). ``floor`` must be ``_KV_BLOCK_PAGES`` for launches that
        go through blockwise chunk attention (its page blocks must divide
        the table width)."""
        need = 1
        for _, req in active:
            need = max(need, (req.kv_len + headroom - 1) // self.page_size + 1)
        maxp = min(
            _pow2_at_least(need, floor=floor),
            self._page_table_padded.shape[1],
        )
        # Sliced from the PADDED buffer: columns past max_pages hold the
        # scratch page (a real pool page), so a bucket that overshoots
        # max_pages gathers junk that attention masks — never an OOB id.
        return self._page_table_padded[:, :maxp]

    def _decode_once(self, inline_allot: list[int] | None = None) -> None:
        g = self.spec_decode_tokens
        if g > 0 or inline_allot is not None:
            self._settle()  # drafts and waves read the rows' own history
        spec = g > 0 and self._spec_ok(g)
        if spec or inline_allot is not None:
            # Draft BEFORE committing to the wide verify launch: when no
            # row's history repeats its tail there is nothing to verify,
            # and the plain step emits the same tokens cheaper.
            # A mixed wave (inline_allot) ALWAYS takes the fused chunk
            # launch — drafted rows verify, undrafted rows ride as
            # width-1 windows, and the inline chunks fill the rest of
            # the chunk width — so speculation and inline prefill
            # compose in one device call.
            drafts: dict[int, np.ndarray] = {}
            sources: dict[int, str] = {}
            for row, req in enumerate(self._rows):
                if req is None:
                    continue
                if spec and self._spec_row_ok(req, g):
                    drafts[row], sources[row] = self._draft_for(req)
                else:
                    drafts[row], sources[row] = req.prompt[:0], "none"
            if inline_allot is not None or any(
                len(d) for d in drafts.values()
            ):
                self._decode_spec_once(
                    g if spec else 0, drafts, sources, inline=inline_allot
                )
                return
        active = self._going_on()
        seeded = self._seeded_launch(r for _, r in active)
        plain = self._plain_launch(active, seeded)
        launch = self._take_prebuilt(active) if plain else None
        if launch is not None:
            self.handover["launches_prebuilt"] += 1
        else:
            # Rows joined or left since the build ahead, or the launch is
            # of another kind: built in the gap, on rows that are settled.
            self._settle("admit" if plain else "launch_kind")
            with self.spans.span("rm.decode.build"):
                # A pp or seeded step is a C=1 chunk (below): blockwise
                # attention needs a KV-block-multiple table width, which
                # the bucket keeps (floor = block).
                launch = self._build_launch(
                    0, preempt=True, chunked=self._pp or seeded
                )
            if launch is None:
                return
            self.handover["launches_rebuilt"] += 1
            active = launch.active
        # What /debug/state shows of the launch: the attention decode_step
        # runs from this process (the Pallas kernel, or the jnp reference
        # where it does not lower) and the launch's size.
        self._last_dispatch = {
            "path": self._decode_path,
            "batch": len(active),
            "bucket": batch_bucket(len(active)),
            "max_len": launch.max_len,
        }
        aw = self.attn_window  # once per launch, whenever it was built
        aw["window_tokens_read"] += launch.window[0]
        aw["window_tokens_skipped"] += launch.window[1]
        aw["decode_context_tokens"] += int(launch.contexts.sum())
        aw["table_pages_max"] = max(aw["table_pages_max"], launch.pt_d.shape[1])
        if launch.latent_tokens:
            attended = int(launch.contexts.sum()) * len(self.cfg.attn_layers)
            aw["latent_tokens_read"] += launch.latent_tokens
            aw["latent_tokens_attended"] += attended
            aw["latent_tokens_shared"] += attended - launch.latent_tokens
            aw["decode_groups"] = launch.groups
        if self.matrix_stats is not None:
            self._note_matrix(
                decode_rows=len(launch.idx), read=self.max_batch,
                written=self.max_batch + launch.boundary_copies,
                boundary_copies=launch.boundary_copies)
        if self.window_stats is not None:
            aw[self._kv_reads] += int(launch.contexts.sum()) * self._kv_readers
            self.window_stats["slots_written"] += len(launch.idx)
        if self.cfg.dsa:
            self._note_sparse(
                "decode", launch.contexts.astype(np.int64),
                launch.pt_d.shape[1] * self.page_size + 1 > self.cfg.index_topk,
            )
        step_t0 = time.monotonic()
        with self.spans.span("rm.decode.dispatch"):
            tokens_d = launch.tokens_d
            if tokens_d is None:
                tokens_d = jnp.asarray(self._tokens)
            if launch.positions_d is not None:
                # A decode step is a C=1 chunk through the layer pipeline
                # (parallel/pp_serving.py) — same page-table attention,
                # same pool scatter, stage weights never move. Seeded
                # launches ride it on every backend: it returns LOGITS,
                # and the replay contract needs the canonical host-side
                # draw.
                self.pool.note_runs(
                    launch.slots[launch.idx], np.ones(len(launch.idx), np.int32))
                states = None
                if launch.state_d is not None:
                    # One token a row: from the working slot, back to it
                    # and to the boundary slot (or slot 0).
                    states = {
                        "state_in": launch.state_d[0],
                        "snap_at": jnp.ones((self.max_batch, 2), jnp.int32),
                        "state_out": launch.state_d.T,
                    }
                if launch.w_slots_d is not None:
                    states = (states or {}) | {
                        "window_slots": launch.w_slots_d[:, None],
                        "window_table": launch.w_pt_d}
                res = self._forward_chunk(
                    tokens_d[:, None],
                    launch.positions_d[:, None],
                    launch.slots_d[:, None],
                    launch.pt_d,
                    launch.lengths_d,
                    _KV_BLOCK_PAGES,
                    states=states,
                )
                logits = self._commit_pool_update(res)[:, 0]
            else:
                res = self._launch(
                    decode_step,
                    self.params,
                    self.cfg,
                    tokens_d,
                    self.pool.kv,
                    launch.slots_d,
                    launch.pt_d,
                    launch.lengths_d,
                    self.page_size,
                    mesh=self.device_mesh,
                    kv_scale=self.pool.kv_scale,
                    **self._pool_kw(),
                    **({} if launch.state_d is None else {
                        "state_slots": launch.state_d}),
                    **({} if launch.w_slots_d is None else {
                        "window_slots": launch.w_slots_d,
                        "window_table": launch.w_pt_d}),
                    **({} if launch.groups_d is None else {
                        "decode_groups": launch.groups_d}),
                )
                logits = self._commit_pool_update(res)
        asked = [(row, r) for row, r in active if r.sampling.return_logits]
        if asked or seeded:
            # Both read logits back before the sampler is launched: the
            # last step's emission goes now, behind the step itself.
            self._flush_emit(_UNDER_DECODE)
        if asked:  # the logits hook: one copy of the launch's logits
            logits_h = np.asarray(logits, dtype=np.float32)
            for row, req in asked:
                req.logit_rows.append(logits_h[row])
                self._keep_expert_ids(req, req.kv_len, row, 1)
                self._keep_selected(req, req.kv_len, row)
        nxt, goes_on, stops = None, False, ()
        if seeded:
            # The canonical per-row (seed, position) draw syncs per row:
            # sample and sync are one span here.
            with self.spans.span("rm.decode.sample"):
                sampled = np.zeros(self.max_batch, dtype=np.int64)
                for row, req in active:
                    sampled[row] = self._sample_seeded_row(req, logits[row])
        else:
            with self.spans.span("rm.decode.sample"):
                if self._launch_draws(r for _, r in active):
                    self._rng, key = jax.random.split(self._rng)
                    sampled_d = sample_tokens(
                        logits, key, temperature=jnp.asarray(self._temps),
                        top_p=jnp.asarray(self._top_ps),
                        top_k=jnp.asarray(self._top_ks),
                    )
                else:
                    sampled_d = sample_tokens(logits, None, draws=False)
            if plain:
                # The device is busy with this launch: the step before's
                # advance — the release and the tree publish of the rows
                # it took to their length cap included — then the next
                # launch's inputs, as if every row had advanced by one.
                # That launch is of the rows that go on: which rows this
                # one takes to a length cap is known before the sync.
                pend, self._pending = self._pending, None
                if pend is not None:
                    h, finished = self.handover, self.stats.finished
                    h["advances_deferred"] += 1
                    self._consume(*pend)
                    h["finishes_deferred"] += self.stats.finished - finished
                ending = frozenset(
                    row for row, r in active
                    if len(r.output_tokens) + 1 >= r.sampling.max_new_tokens
                    or r.num_tokens + 1 >= self.max_seq_len
                )
                goes_on = len(ending) < len(active)
                if goes_on:
                    with self.spans.span("rm.decode.build"):
                        nxt = self._build_launch(1, preempt=False, ending=ending)
                if nxt is not None:
                    stops = [
                        (row, r.sampling.stop_token_ids)
                        for row, r in nxt.active
                        if r.sampling.stop_token_ids
                    ]
            # Wake-ups last: the handlers take the GIL from nothing.
            self._flush_emit(_UNDER_DECODE)
            with self.spans.span("rm.decode.sync"):  # the wait for the device
                sampled = np.asarray(sampled_d)
        # sample_tokens materialized on host above, so this spans the full
        # dispatch+device time of the step — the per-token latency (TPOT)
        # seen by every active request.
        stamp = time.monotonic()
        elapsed = stamp - step_t0
        self._emit_steps.append((
            step_t0,
            elapsed,
            1,
            ("decode", len(active), self.max_batch, elapsed, len(active)),
            [
                (r.trace, {"k_steps": 1, "batch_rows": len(active)})
                for _, r in active
                if r.trace is not None
            ],
        ))
        self._prebuilt = nxt
        step = (active, launch.slots, sampled, stamp)
        why = self._settle_why(plain, goes_on, nxt, stops, sampled)
        if why is None:
            # The hand-over: the ids the next launch feeds, nothing else —
            # in self._tokens, and for that launch as they lie on the
            # device (the id of an idle row, or of one that ends here, is
            # read by nothing).
            self._tokens[nxt.idx] = sampled[nxt.idx]
            nxt.tokens_d = sampled_d
            self._pending = step
            return
        self.handover["advances_settled"] += 1
        self.handover["settled_for"][why] += 1
        self._consume(*step)

    def _plain_launch(self, active: list[tuple], seeded: bool) -> bool:
        """Is this decode launch the plain one — ``decode_step`` and the
        batched sampler, no row seeded (all-seeded launches draw per row
        on the host), no row keeping logits, no speculation configured,
        no pp mesh? (An inline wave never comes here.) Only such a launch
        hands over fast; see :meth:`_settle_why`."""
        return not (
            seeded
            or self._pp
            or self.spec_decode_tokens
            or any(r.sampling.return_logits for _, r in active)
        )

    @staticmethod
    def _settle_why(plain, goes_on, nxt, stops, sampled) -> str | None:
        """THE rule for the hand-over between two decode launches, from
        what the launch and its rows are (no knob): None — keep the
        step's advance for behind the next launch — iff the launch is
        plain (:meth:`_plain_launch`), a row goes on (``goes_on``), the
        next launch's inputs are built (``nxt``) and no row finishes
        that the next launch holds. A row that reaches a length cap is
        known before this launch is sent, so it is not in ``nxt``
        (``_DecodeLaunch.ending``) and its finish — the release, the tree
        publish — waits with the advance; a row that sampled one of its
        stop ids (``stops``: the rows of ``nxt`` that have any) is known
        only after the sync. Else the key of ``handover["settled_for"]``
        to count the step under: it settles now, in the order every step
        had before PR 30. Either way a finished row never rides another
        launch."""
        if not plain:
            return "launch_kind"
        if not goes_on:
            return "finish"  # every row ends by length: nothing to build
        if nxt is None:
            return "no_pages"
        if any(int(sampled[row]) in ids for row, ids in stops):
            return "finish"
        return None

    def _build_launch(
        self, ahead: int, preempt: bool, chunked: bool = False,
        ending: frozenset[int] = frozenset(),
    ) -> _DecodeLaunch | None:
        """The inputs of the decode launch that writes position ``kv_len +
        ahead`` of every live row: 0 for the rows as they are, 1 behind a
        launch that is still on the device, as if each row had advanced
        by one. The rows that launch takes to their length cap
        (``ending``) are none of this one's: no page, window page or
        state slot is taken for them, and they hold an idle row's scratch
        values, so what the launch on the device leaves of them — rows,
        state, window tail — is what their release hands to the tree.
        ``preempt`` is the ordinary build's: a row the pool has
        no page for is preempted. A build ahead never preempts — it
        returns None and the ordinary build decides once the rows are
        settled; the pages it had taken by then stay with their requests
        (:meth:`_ensure_page`), and a later build finds them in the table
        instead of taking a second. None too when no row is left."""
        ps = self.page_size
        slots = np.full(self.max_batch, self._scratch_slot, dtype=np.int32)
        lengths = np.ones(self.max_batch, dtype=np.int32)
        stateful = self.state_stats is not None
        state_slots = np.zeros((2, self.max_batch), dtype=np.int32)
        windowed = self.window_stats is not None
        w_slots = np.zeros(self.max_batch, dtype=np.int32)
        starved: list[Request] = []
        for row, req in enumerate(self._rows):
            if req is None or row in ending:
                continue
            page_idx, offset = divmod(req.kv_len + ahead, ps)
            page = self._ensure_page(row, req, page_idx)
            if page is not None and windowed:
                w_page = self._ensure_window_page(row, req, page_idx)
                if w_page is None:
                    page = None
                else:
                    w_slots[row] = w_page * ps + offset
            if page is None:  # crossing into a fresh page, and none to take
                if not preempt:
                    return None
                starved.append(req)
                continue
            slots[row] = page * ps + offset
            lengths[row] = req.kv_len + ahead + 1
            if stateful:
                # The new state goes back to the working slot, and is kept
                # in the boundary slot when this token completes a page.
                state_slots[0, row] = req.state_slot
                if lengths[row] % ps == 0:
                    state_slots[1, row] = req.state_boundary
        for req in starved:
            self._preempt(req)
        active = self._live_rows(ending)
        if not active:
            return None
        idx = np.fromiter((row for row, _ in active), np.intp, len(active))
        window = (0, 0)
        if self._sliding_layers:
            ctx = lengths[idx]
            read = np.minimum(ctx, self.cfg.sliding_window)
            window = (
                int(read.sum()) * self._sliding_layers,
                int((ctx - read).sum()) * self._sliding_layers,
            )
        pt = (self._decode_pt_bucket(active, 1 + ahead, floor=_KV_BLOCK_PAGES)
              if chunked else self._decode_pt_bucket(active, 1 + ahead))
        if windowed:
            held = [len(r.w_pages) * ps for _, r in active]
            ws = self.window_stats
            ws["slots_held_by_rows"] = sum(held)
            ws["held_peak_row"] = max(ws["held_peak_row"], max(held))
        latent_tokens, groups, groups_d = 0, None, None
        if self.cfg.latent:
            latent_tokens = int(lengths[idx].sum())
            groups = _NO_GROUPS
            # Where the latent decode kernel serves (not the C=1 chunk, not
            # the selecting programs), it reads what rows share once.
            if not chunked and not (
                    self.cfg.dsa and pt.shape[1] * ps + 1 > self.cfg.index_topk):
                groups, groups_d = self._grouping(active, pt, lengths, idx)
                latent_tokens -= groups["shared_tokens"]
            latent_tokens *= len(self.cfg.attn_layers)
        return _DecodeLaunch(
            active=active,
            sig=[(row, r.rid, r.kv_len + ahead) for row, r in active],
            ending=ending,
            idx=idx,
            slots=slots,
            max_len=int(lengths[idx].max()) - 1,
            window=window,
            latent_tokens=latent_tokens,
            contexts=lengths[idx],
            slots_d=jnp.asarray(slots),
            pt_d=jnp.asarray(pt),
            lengths_d=jnp.asarray(lengths),
            positions_d=jnp.asarray(lengths - 1) if chunked else None,
            state_d=jnp.asarray(state_slots) if stateful else None,
            boundary_copies=int(np.count_nonzero(state_slots[1])),
            w_slots_d=jnp.asarray(w_slots) if windowed else None,
            w_pt_d=jnp.asarray(
                self._w_page_table_padded[:, : pt.shape[1]]) if windowed else None,
            groups=groups,
            groups_d=groups_d,
        )

    def _grouping(
        self, active: list[tuple], pt: np.ndarray, lengths: np.ndarray,
        idx: np.ndarray,
    ) -> tuple[dict, jax.Array]:
        """``decode_groups`` of a launch of these rows, as counts and as
        lines on the device. The first pages of a row's table are laid
        once, when the row is installed, and contexts only grow: the groups
        of one set of (row, rid) under one table width hold until a row
        joins or leaves, so a build pays a comparison of two tuples, and
        neither the table's comparison nor an upload."""
        key = (tuple((row, r.rid) for row, r in active), pt.shape[1])
        if self._groups_for is None or self._groups_for[0] != key:
            groups, stats = decode_groups(pt, lengths, idx, self.page_size)
            self._groups_for = (key, stats, jnp.asarray(groups))
        return self._groups_for[1:]

    def _take_prebuilt(self, active: list[tuple]) -> _DecodeLaunch | None:
        """The inputs built ahead, iff they were built for exactly these
        rows — the rows that go on (:meth:`_going_on`), not the live ones:
        a row the pending advance ends is none of theirs — at the
        positions their tokens go to now (one further while the last
        step's advance is pending); they are used once."""
        pre, self._prebuilt = self._prebuilt, None
        if pre is None:
            return None
        ahead = 0 if self._pending is None else 1
        now = [(row, r.rid, r.kv_len + ahead) for row, r in active]
        return pre if pre.sig == now else None

    def _consume(
        self, active: list[tuple], slots: np.ndarray, sampled: np.ndarray,
        stamp: float,
    ) -> None:
        """One decode step's per-row advance; the step is counted where
        its tokens are, so ``generated_tokens`` over ``decode_steps``
        reads whole steps at any instant."""
        with self.spans.span("rm.decode.consume"):
            self.stats.decode_steps += 1
            for row, req in active:
                self._advance_token(
                    req, row, int(slots[row]), int(sampled[row]), stamp
                )

    def _settle(self, why: str = "launch_kind") -> None:
        """Run the pending advance now (idempotent; nothing pending:
        nothing done). Everything that reads or changes the rows other
        than a plain decode launch of the rows that go on calls this
        first — admission into a free row, ``cancel`` / ``cancel_all``,
        the drains, ``_preempt``, a wave or a speculative launch,
        ``generate()``'s end — so each sees the rows as they were when
        every step advanced its own: a row the pending step took to its
        length cap is finished and gone (``cancel`` looks again at the
        request it was called for). ``why`` (a key of
        ``handover["settled_for"]``) says what forced it; the default is
        for whatever follows that is not such a launch."""
        pend, self._pending = self._pending, None
        if pend is None:
            return
        self.handover["advances_settled"] += 1
        self.handover["settled_for"][why] += 1
        self._consume(*pend)

    def _spec_ok(self, g: int) -> bool:
        """Speculative decoding is considered whenever rows are active and
        no request waits for admission (admission happens between
        launches, so a wide verify launch would delay a queued prefill).
        Stochastic rows verify by exact rejection sampling
        (``ops/sampling.py::spec_verify_sample``), so temperature does not
        disable the path. Budget and headroom limits are per-row
        (``_spec_row_ok``): a nearly-finished request rides the launch
        with an empty draft — exactly a plain step for that row — instead
        of switching speculation off for the whole batch. Under pp the
        verify chunk rides the pipeline schedule for ANY batch size
        (``_pp_n_micro`` falls back to one wave when the batch doesn't
        split into pp microbatches — single-stream serving, speculation's
        prime latency case, must not lose it)."""
        if self.waiting:
            return False
        if any(
            r is not None and r.sampling.seed is not None
            for r in self._rows
        ):
            # Seeded replay (crash recovery): the spec verify resample
            # draws from the launch-wide key, which would decouple a
            # seeded row's tokens from its (seed, position) schedule —
            # seeded batches take the position-keyed single-step path.
            return False
        return any(req is not None for req in self._rows)

    def _spec_row_ok(self, req: Request, g: int) -> bool:
        """Per-row speculation gate: the verify window needs γ+1 positions
        of sequence and page-table headroom, and a row within one token of
        its output budget gains nothing from a draft (the surplus would be
        discarded). Failing rows decode normally inside the launch via an
        empty draft."""
        if req.kv_len + g + 1 > self.max_seq_len:
            return False
        if (req.kv_len + g) // self.page_size >= self.max_pages:
            return False
        if req.sampling.max_new_tokens - len(req.output_tokens) < 2:
            return False
        return True

    # Draft lookup scans at most this many trailing history tokens: the
    # match quality of prompt lookup lives in the recent context, and an
    # unbounded scan would put O(total-context) host work on the
    # inter-launch critical path of a 32k-token generation.
    _SPEC_WINDOW = 1024

    def _draft_for(self, req: Request) -> tuple[np.ndarray, str]:
        """Returns ``(draft, source)`` — source ∈ DRAFT_SOURCES, the
        speculation ledger's per-class key (tree drafts and n-gram
        drafts have very different acceptance profiles, and tuning γ on
        their blend hides which drafter is actually paying)."""
        # γ for this request's class: the configured window, shrunk or
        # regrown per (tenant, shape) by the acceptance-adaptive
        # controller when --spec-adaptive is on (clamped to [1, base];
        # base 0 — including the SLO tier-1 spec-off — always wins).
        gamma = self.spec_ledger.gamma_for(
            req.tenant, shape_bucket(len(req.prompt)),
            self.spec_decode_tokens,
        )
        hist = self._sequence_key(req, req.kv_len + 1)
        # Best drafter first: the radix tree itself. A replayed
        # conversation (same prompt served before) finds the PREVIOUS
        # generation's published tokens cached beyond its history — a
        # near-perfect draft for greedy replays, and the mechanism that
        # makes speculation a property of the prefix cache rather than of
        # the request's own text. The walk is O(context), so it only runs
        # for requests that admitted as near-full prefix hits (replay
        # candidates) and stops the first time it comes back empty —
        # novel generations never pay it per launch (_SPEC_WINDOW bounds
        # their n-gram scan instead).
        # Draft-ahead from the mesh (ROADMAP 1a′): a PREFETCH fill or a
        # disk promotion may have attached a continuation AFTER this
        # request's last peek latched tree drafting off — the tree's
        # draft_ready_epoch (bumped by kv_transfer's apply site) says so
        # without a walk. Re-arm and peek again, so a remote/disk-
        # resident hit drafts exactly like a natively-published one.
        epoch = getattr(self.tree, "draft_ready_epoch", 0)
        promoted = epoch > req.draft_epoch
        if promoted:
            req.tree_draft_ok = True
            req.draft_epoch = epoch
        if req.tree_draft_ok and (
            promoted
            or req.prefix_len >= max(0, len(req.prompt) - self.page_size)
        ):
            cont = self.tree.peek_continuation(hist, gamma)
            if len(cont):
                return cont, "tree"
            req.tree_draft_ok = False
        draft = self._ngram_draft(
            hist[-self._SPEC_WINDOW :], gamma, self.spec_ngram
        )
        return draft, ("ngram" if len(draft) else "none")

    @staticmethod
    def _ngram_draft(hist: np.ndarray, gamma: int, n: int) -> np.ndarray:
        """Prompt-lookup draft: the ``gamma`` tokens that followed the most
        recent PREVIOUS occurrence of the current tail n-gram (falling back
        to bigrams). Empty when the history never repeats its tail."""
        L = len(hist)
        for nn in range(n, 1, -1):
            if L <= nn:
                continue
            tail = hist[L - nn:]
            win = np.lib.stride_tricks.sliding_window_view(hist, nn)
            hits = np.nonzero((win[: L - nn] == tail).all(axis=1))[0]
            if hits.size:
                j = int(hits[-1]) + nn  # continuation of the match
                return hist[j : j + gamma]
        return hist[:0]

    def _live_rows(
        self, less: frozenset[int] = frozenset()
    ) -> list[tuple[int, "Request"]]:
        return [(row, r) for row, r in enumerate(self._rows)
                if r is not None and row not in less]

    def _going_on(self) -> list[tuple[int, "Request"]]:
        """The rows the next decode launch is of: the live rows, less
        those the pending advance ends (the launch built behind that
        step says which: they stay in ``_rows`` until the advance has
        run)."""
        if self._pending is None:
            return self._live_rows()
        return self._live_rows(self._prebuilt.ending)

    def _ensure_page(self, row: int, req: Request, page_idx: int) -> int | None:
        """The pool page behind entry ``page_idx`` of the row's table,
        taken from the pool (evicting under pressure) where the row has
        none yet; None when the pool has none to give. A page taken is the
        request's at once (``own_slots``): ``_release`` returns it."""
        page = int(self._page_table[row, page_idx])
        if page != self._scratch_page:
            return page  # already provisioned
        new = self._alloc_pages(1, cause="preempt")
        if new is None:
            return None
        req.own_slots = np.concatenate([req.own_slots, new])
        page = int(new[0]) // self.page_size
        self._page_table[row, page_idx] = page
        return page

    def _ensure_window_page(self, row: int, req: Request, page_idx: int) -> int | None:
        """:meth:`_ensure_page` for the window group: the page behind
        logical page ``page_idx`` of the row, taken (one count, the row's)
        where the row has none yet; None when the group has none to give."""
        at = page_idx - req.w_first
        if at < len(req.w_pages):
            return req.w_pages[at]
        new = self._alloc_window(1)
        if new is None:
            return None
        req.w_pages.append(int(new[0]))
        self._w_page_table_padded[row, page_idx] = new[0]
        return int(new[0])

    def _provision_rows(
        self, extra: int, extras: dict[int, int] | None = None
    ) -> list[tuple[int, "Request"]]:
        """Ensure every active row's page table covers positions
        ``kv_len .. kv_len+extra``; preempt rows the pool can't cover.
        Returns the surviving (row, request) pairs. ``extras`` overrides
        the bound per row — the speculative path provisions only each
        row's actual draft window, so a row that opted out (empty draft)
        cannot be preempted for headroom it will never write."""
        ps = self.page_size
        preempted: list[Request] = []
        for row, req in enumerate(self._rows):
            if req is None:
                continue
            row_extra = extra if extras is None else extras.get(row, extra)
            for p_idx in range(req.kv_len // ps, (req.kv_len + row_extra) // ps + 1):
                if self._ensure_page(row, req, p_idx) is None:
                    preempted.append(req)
                    break
        for req in preempted:
            self._preempt(req)
        return self._live_rows()

    def _decode_spec_once(
        self,
        g: int,
        drafts: dict[int, np.ndarray],
        sources: dict[int, str] | None = None,
        inline: list[int] | None = None,
        decode: bool = True,
    ) -> None:
        """One fused chunk launch: decode rows verify [fed_token, draft…]
        (w=draft+1 live positions per row; w=1 = a plain step) and —
        mixed compute waves — inline prefill jobs ride the SAME call as
        rows whose live window is their allotted slice of prompt tokens
        (``inline`` = tokens per backlog job, from WaveScheduler.plan).
        Acceptance per decode row via ``spec_verify_sample`` — greedy
        rows take the longest argmax-matching draft prefix, stochastic
        rows accept each draft token with its target probability (exact
        rejection sampling) — and emit one bonus token. Fed positions'
        K/V is written by the pass itself, so accepted tokens cost no
        extra work; rejected positions hold stale K/V that the next
        launch overwrites (slots are purely positional) and that
        attention never reads (masked by length). An inline job whose
        final chunk lands here installs + finalizes its first token in
        the same wave. ``decode=False`` (prefill/boost waves, the
        all-seeded fallback's second launch) advances the backlog alone."""
        ps = self.page_size
        with self.spans.span("rm.decode.build"):
            jobs = (
                [
                    (job, w)
                    for job, w in zip(self._inline, inline)
                    if w > 0 and job.pos < job.total
                ]
                if inline is not None
                else []
            )
            if inline is None:
                C = g + 1  # legacy speculative shape, untouched
            else:
                # Chunk width covers the widest live window this wave —
                # pow2-bucketed so varying allotments reuse compiled
                # variants (floor matches _prefill_group's chunk floor).
                C = _pow2_at_least(
                    max([g + 1] + [w for _, w in jobs]), floor=16
                )
            # Provision only each row's actual verify window (draft + bonus):
            # an opted-out row (empty draft) needs exactly the one position a
            # plain step would, so γ positions of headroom it lacks must not
            # preempt it. Inline jobs never provision — their pages were all
            # acquired up front at admission.
            active = (
                self._provision_rows(
                    g, extras={row: len(d) for row, d in drafts.items()}
                )
                if decode
                else []
            )
            if not active and not jobs:
                return
            step_t0 = time.monotonic()

            B = self.max_batch
            kv_block = _KV_BLOCK_PAGES
            maxp = _pow2_at_least(
                max(
                    [
                        (r.kv_len + len(drafts.get(row, r.prompt[:0]))) // ps
                        + 1
                        for row, r in active
                    ]
                    + [(job.pos + w) // ps + 1 for job, w in jobs]
                ),
                floor=kv_block,
            )
            toks = np.zeros((B, C), dtype=np.int32)
            draft_len = np.zeros((B,), dtype=np.int32)
            sl = np.full((B, C), self._scratch_slot, dtype=np.int32)
            poss = np.zeros((B, C), dtype=np.int32)
            kvlen = np.zeros((B,), dtype=np.int32)
            pt = np.full((B, maxp), self._scratch_page, dtype=np.int32)
            for row, req in active:
                draft = drafts.get(row, req.prompt[:0])
                drafts[row] = draft
                w = len(draft) + 1  # this row's live verify window
                toks[row, 0] = self._tokens[row]
                toks[row, 1 : 1 + len(draft)] = draft
                pos = req.kv_len + np.arange(C, dtype=np.int32)
                poss[row] = np.minimum(pos, self.max_seq_len - 1)
                n_pages = min((req.kv_len + len(draft)) // ps + 1, self.max_pages)
                pt[row, :n_pages] = self._page_table[row, :n_pages]
                # Positions past the row's window write their (garbage) K/V to
                # the scratch slot; causal masking keeps them out of every
                # logit the verify actually uses.
                sl[row, :w] = pt[row, pos[:w] // ps] * ps + pos[:w] % ps
                kvlen[row] = req.kv_len + w
                draft_len[row] = len(draft)
                # Conservation contract: a draft counts as PROPOSED here iff
                # its row survives to the verify below — every proposed
                # token is then accounted accepted or rejected in the
                # accept loop, so proposed == accepted + rejected holds on
                # every path (drafts of rows preempted by _provision_rows
                # above were never proposed). The per-class ledger rides the
                # same two sites, so it cannot undercount either.
                self.stats.spec_proposed += len(draft)
                self._m_spec_proposed.inc(len(draft))
                if len(draft):
                    cell = self._spec_shape.setdefault(
                        shape_bucket(len(req.prompt)), [0, 0]
                    )
                    cell[0] += len(draft)

            for job, w in jobs:
                # Inline prefill rows: the live window is the job's allotted
                # prompt slice [pos, pos+w) — exactly a _prefill_group chunk
                # for one row, riding the decode launch. draft_len stays 0,
                # so the verify below treats the row as undrafted and its
                # (meaningless mid-prompt) bonus sample is never consumed.
                row, pos, prompt = job.row, job.pos, job.req.prompt
                toks[row, :w] = prompt[pos : pos + w]
                p = pos + np.arange(C, dtype=np.int32)
                poss[row] = np.minimum(p, self.max_seq_len - 1)
                sl[row, :w] = job.token_slots[pos : pos + w]
                kvlen[row] = pos + w
                npg = min(-(-job.total // ps), maxp)
                pt[row, :npg] = job.token_slots[::ps][:npg] // ps
            self.pool.note_runs(sl[:, 0], (sl != self._scratch_slot).sum(axis=1))
            toks_d, poss_d, sl_d = (
                jnp.asarray(toks), jnp.asarray(poss), jnp.asarray(sl)
            )
            pt_d, kvlen_d = jnp.asarray(pt), jnp.asarray(kvlen)

        # The verify pass is just a C-wide chunk; _forward_chunk picks
        # the pipeline schedule under pp (parallel/pp_serving.py).
        with self.spans.span("rm.decode.dispatch"):
            res = self._forward_chunk(
                toks_d, poss_d, sl_d, pt_d, kvlen_d, kv_block
            )
            logits = self._commit_pool_update(res)
        if active:
            with self.spans.span("rm.decode.sample"):
                draws = self._launch_draws(r for _, r in active)
                key = None
                if draws:
                    self._rng, key = jax.random.split(self._rng)
                accept_len, bonus = spec_verify_sample(
                    logits,
                    jnp.asarray(toks[:, 1:]),
                    jnp.asarray(draft_len),
                    key,
                    jnp.asarray(self._temps),
                    jnp.asarray(self._top_ps),
                    jnp.asarray(self._top_ks),
                    draws=draws,
                )
        # A wave without decode rows is a prefill launch to hide under.
        self._flush_emit(_UNDER_DECODE if active else _UNDER_PREFILL)
        if active:
            with self.spans.span("rm.decode.sync"):
                accept_len = np.asarray(accept_len)  # [B] one sync
                bonus = np.asarray(bonus)
            self.stats.decode_steps += 1

        emitted_total = 0
        stamp = time.monotonic()
        with self.spans.span("rm.decode.consume"):
            for row, req in active:
                draft = drafts[row]
                a = int(accept_len[row])
                rejected = len(draft) - a
                self.stats.spec_accepted += a
                self.stats.spec_rejected += rejected
                self._m_spec_accepted.inc(a)
                if rejected:
                    self._m_spec_rejected.inc(rejected)
                # Rejected tail: the gap before this row's NEXT token
                # includes re-decoding it — the spec_verify_miss stall
                # attribution (consumed by _stall_cause).
                req.spec_miss = rejected
                if a:
                    cell = self._spec_shape.setdefault(
                        shape_bucket(len(req.prompt)), [0, 0]
                    )
                    cell[1] += a
                if len(draft):
                    self.spec_ledger.note_wave(
                        req.tenant,
                        shape_bucket(len(req.prompt)),
                        sources.get(row, "ngram") if sources else "ngram",
                        len(draft),
                        a,
                        len(draft),
                    )
                base = req.kv_len
                for i in range(a + 1):  # a accepted drafts + 1 bonus token
                    pos = base + i
                    slot = int(self._page_table[row, pos // ps] * ps + pos % ps)
                    token = int(draft[i]) if i < a else int(bonus[row])
                    emitted_total += 1
                    if self._advance_token(req, row, slot, token, stamp):
                        break
        inline_tok = 0
        pending: list[tuple] = []
        for job, w in jobs:
            start = job.pos
            job.pos += w  # exact chunk resume offset for the next wave
            inline_tok += w
            tr = job.req.trace
            if tr is not None:
                tr.add(
                    "prefill_inline", step_t0,
                    time.monotonic() - step_t0, cat="prefill",
                    chunk_tokens=int(w), resume_offset=int(start),
                )
            if job.pos >= job.total:
                # Final chunk: install + hand the last prompt position's
                # logits to the shared first-token finalizer (one
                # batched sample for every job finishing this wave).
                req = job.req
                req.output_tokens = []
                req.kv_len = job.total
                req.token_slots = job.token_slots
                req.own_slots = job.own
                self._inline_rows.discard(job.row)
                self._install_prefilled(
                    req, job.row, job.reuse, inline=True
                )
                pending.append((req, logits[job.row, w - 1]))
        if jobs:
            # Stall attribution: inline chunks advanced inside this wave
            # (finished or not) — a decode gap spanning this instant is
            # prefill_inline, never scheduler_wait (and not a convoy).
            self._last_inline_prefill_t = time.monotonic()
            self._inline = [j for j in self._inline if j.pos < j.total]
        if pending:
            # The finalizer waits for the device with nothing queued on
            # it: this wave's tokens go first.
            self._flush_emit(_EXPOSED)
            with self.spans.span("rm.first_token.sync"):
                self._finalize_first_tokens(pending)
        elapsed = time.monotonic() - step_t0
        # The launch processes B·C positions; the USEFUL work is the
        # accepted+bonus decode tokens actually emitted plus the inline
        # prefill tokens advanced.
        self._emit_steps.append((
            step_t0,
            elapsed,
            max(emitted_total, 1) if active else 0,
            (
                "decode" if active else "prefill",
                emitted_total + inline_tok,
                B * C,
                elapsed,
                len(active) + len(jobs),
            ),
            [
                (
                    r.trace,
                    {
                        "k_steps": 1,
                        "batch_rows": len(active),
                        "speculative": True,
                        "draft_tokens": int(draft_len[row]),
                        "accepted_tokens": int(accept_len[row]),
                    },
                )
                for row, r in active
                if r.trace is not None
            ],
        ))

    def hint_stall(self, cause: str) -> None:
        """One-shot stall-cause latch for external planes: the next
        over-threshold inter-token gap is attributed to ``cause``
        instead of the engine's own inference. The rebalance executor
        latches ``rebalance_handoff`` here while an ownership move
        drains this node's shard."""
        from radixmesh_tpu.obs.token_timeline import STALL_CAUSES

        if cause not in STALL_CAUSES:
            raise ValueError(f"unknown stall cause {cause!r}")
        self._stall_hint = cause

    def _stall_cause(self, req: Request, now: float, gap_s: float) -> str:
        """Attribute one over-threshold inter-token gap to the single
        most likely cause, in the taxonomy's priority order (see
        obs/token_timeline.py::STALL_CAUSES)."""
        hint = self._stall_hint
        if hint is not None:
            self._stall_hint = None
            return hint
        if self._restoring:
            return "restore_park"
        # ``now`` is the step's stamp, and the flush may run behind a
        # LATER prefill's dispatch: a launch after ``now`` is not in the gap.
        if 0.0 <= now - self._last_prefill_t <= gap_s:
            # A prefill wave launched inside the gap: the decode convoy.
            return "prefill_convoy"
        if 0.0 <= now - self._last_inline_prefill_t <= gap_s:
            # An inline prefill chunk (mixed compute wave) launched
            # inside the gap: budget-bounded by design, so it is NOT a
            # convoy — before this branch existed, a gap spanning a
            # wave boundary with an inline chunk in it fell through to
            # scheduler_wait, hiding the interleave's (bounded) cost.
            return "prefill_inline"
        if req.spec_miss:
            req.spec_miss = 0
            return "spec_verify_miss"
        return "scheduler_wait"

    def _note_token_time(self, req: Request, now: float) -> None:
        """Per-emitted-token timeline/goodput accounting, ``now`` being
        the stamp of the step that made the token. The FIRST token of a
        request only stamps the clock (its latency is TTFT, not ITL);
        every later token records its inter-token gap, with
        over-threshold gaps attributed to a stall cause."""
        prev = req.last_token_time
        req.last_token_time = now
        self.goodput.note_token(req.tenant)
        if not prev:
            return
        gap = now - prev
        cause = None
        if gap >= self.timeline.stall_threshold_s:
            cause = self._stall_cause(req, now, gap)
            self.goodput.note_stall(req.tenant, gap)
        self.timeline.note_token(
            req.rid, req.tenant, gap, cause,
            trace_id=getattr(req.trace, "trace_id", None), now=now,
        )

    def _advance_token(
        self, req: Request, row: int, slot: int, token: int, stamp: float
    ) -> bool:
        """One row's half of a decode step's advance (:meth:`_consume`):
        the fed token's KV landed at ``slot``, ``token`` was sampled in
        the step stamped ``stamp``. The next launch needs none of it but
        the token id and whether the row finished, so between two plain
        launches it runs behind that launch. Returns True when the
        request finished (stop token / length cap) and was released —
        shared by every decode path so the subtle stop/stats bookkeeping
        cannot drift between them. What only observers need (the output
        counter, the token timeline, the stream wake-up) is queued for
        :meth:`_flush_emit`; a finish wakes its waiters at once, through
        ``Request.__setattr__``."""
        req.append_slot(slot)
        req.kv_len += 1
        if ((req.state_boundary or self.tail_stats is not None)
                and req.kv_len % self.page_size == 0):
            # The step completed a page: its state is in the boundary slot
            # (a tail is the pages the row holds there: nothing to keep).
            req.state_boundary_len = req.kv_len
            if self.window_stats is not None:
                self._release_window(req, req.kv_len)
        req.output_tokens.append(token)
        self.stats.generated_tokens += 1
        finished = (
            req.is_finished_by(token) or req.num_tokens >= self.max_seq_len
        )
        # Don't count the terminal token as output if it's a stop.
        stop = finished and token in req.sampling.stop_token_ids
        self._emit_rows.append((req, stamp, not stop))
        if finished:
            if stop:
                req.output_tokens.pop()
                self.stats.generated_tokens -= 1
            if req.submit_time:
                self._m_req_e2e.labels(tenant=req.tenant).observe(
                    stamp - req.submit_time,
                    trace_id=getattr(req.trace, "trace_id", None),
                )
            req.state = RequestState.FINISHED
            self.stats.finished += 1
            self._release(req)
            self._pressure = False  # freed memory: resume admission
            return True
        self._tokens[row] = token
        if (
            self.stream_publish_tokens > 0
            and len(req.output_tokens) % self.stream_publish_tokens == 0
        ):
            # Mid-decode publish (crash recovery): the grown prefix
            # (prompt + generated-so-far) lands in the tree AND
            # replicates around the ring every N tokens, so a node death
            # loses at most N tokens of resurrection cache hit — the
            # re-prefill on a surviving replica is near-pure hit. Same
            # call _preempt makes; idempotent for already-published
            # prefixes.
            self._publish(req, req.kv_len)
        return False

    def _flush_emit(self, under: str, only: Request | None = None) -> None:
        """The deferred half of the decode steps since the last flush:
        each token's output counter, timeline entry and stream wake-up
        (``Request.note_progress``), and each launch's latency samples,
        step-plane wave and trace spans. None of it is read by a launch,
        so the decode paths call this once the NEXT launch is on the
        device and before they wait for it — the streams' writer (the
        frontend's one emitter thread, or a waiter's handler thread) then
        takes the GIL while the chip is busy. A token is never held across
        a wait: a step that prefills flushes behind that prefill's
        dispatch, a step that leaves no row flushes at its end, and
        preemption / cancel flush the request concerned (``only``; the
        launch records stay queued). ``under`` names the counter of
        ``self.emit`` the flush is counted in."""
        if only is None:
            rows, self._emit_rows = self._emit_rows, []
            steps, self._emit_steps = self._emit_steps, []
        else:
            rows = [e for e in self._emit_rows if e[0] is only]
            steps = []
            if rows:
                self._emit_rows = [
                    e for e in self._emit_rows if e[0] is not only
                ]
        if not rows and not steps:
            return
        with self.spans.span("rm.decode.emit"):
            for step_t0, elapsed, samples, wave, chunks in steps:
                for _ in range(samples):
                    self._note_decode_time(elapsed / samples)
                if self.step_acct is not None:
                    self.step_acct.note_wave(*wave)
                for tr, attrs in chunks:
                    tr.add(
                        "decode_chunk", step_t0, elapsed, cat="decode",
                        **attrs,
                    )
            timeline_on = self.timeline is not None  # one branch when off
            emitted = 0
            for req, stamp, counted in rows:
                if counted:
                    self._m_generated.inc()
                    emitted += 1
                if timeline_on:
                    self._note_token_time(req, stamp)
                if req.state is RequestState.RUNNING:
                    req.note_progress()
            self.emit["tokens"] += emitted
            self.emit[under] += 1

    def _preempt(self, req: Request) -> None:
        """Pool exhausted mid-decode even after eviction: publish what we
        have, free the row, and requeue from scratch (the generated tokens
        are discarded; the published KV makes the retry a long prefix hit)."""
        self._settle("preempt")
        self.stats.preemptions += 1
        self._m_preempt.inc()
        self._pressure = True
        # Its queued tokens belong to the life that ends here.
        self._flush_emit(_EXPOSED, only=req)
        req.requeue_time = time.monotonic()
        tr = req.trace
        if tr is not None:
            tr.add(
                "preempt", req.requeue_time, 0.0, cat="scheduler",
                kv_len=int(req.kv_len),
                output_tokens=len(req.output_tokens),
            )
        self._release(req)
        req.state = RequestState.QUEUED
        req.output_tokens = []
        # Token-timeline clock resets with the life: the retry's first
        # token is TTFT again, not a giant inter-token gap.
        req.last_token_time = 0.0
        req.spec_miss = 0
        req.kv_len = 0
        req.prefix_len = 0
        req.token_slots = np.empty(0, dtype=np.int32)
        # The retry re-admits against its own just-published generation —
        # the ideal tree-draft replay — so re-enable tree drafting even if
        # the first life gave up on it.
        req.tree_draft_ok = True
        self.waiting.insert(0, req)
