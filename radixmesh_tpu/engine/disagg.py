"""Disaggregated prefill/decode serving with KV-page handoff.

The reference's core design is disaggregation-by-role: distinct PREFILL and
DECODE node roles (``radix/core_enum.py:4-7``) with role-aware routing
(``radix_mesh.py:219-238``) — but it never moves KV between them, because it
has no model; only slot *indices* replicate. SURVEY §7 stage 6 makes the
handoff real for the TPU stack: a prefill worker computes the prompt's KV,
ships the pages to a decode worker's pool, and decode continues generation
against its own HBM.

Two transfer paths, per SURVEY §5 "distributed communication backend":

- **DCN / cross-slice** (this module): the prompt KV is packed into a
  length-framed bytes message and sent over any :class:`Communicator`
  (in-process, Python TCP, or the native C++ transport) — the same control
  plane the oplog ring uses. Framing is a fixed-width JSON header (shapes,
  dtype, sampling, timing) + raw page bytes; bfloat16 round-trips via
  ml_dtypes.
- **ICI / intra-slice** (``parallel/kv_transfer.py``): when prefill and
  decode shards sit on one TPU slice, the page block moves with a jitted
  ``ppermute`` instead of touching the host.

The decode side re-checks its *own* radix cache before writing the shipped
pages: token-identical prefixes already cached locally are reused and only
the tail is written. To save the *bandwidth* too (not just the pool
writes), the prefill side can ship a tail-only packet: query
:meth:`DecodeWorker.cached_prefix_len` (or track it via the oplog ring's
router replica) and pass ``skip_prefix`` to
:meth:`PrefillWorker.prefill_handoff`; the packet then carries KV only for
``prompt[kv_start:]``.
"""

from __future__ import annotations

import json
import threading
import time
from dataclasses import dataclass, field
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from radixmesh_tpu.comm.communicator import Communicator
from radixmesh_tpu.engine.engine import Engine, _pow2_at_least
from radixmesh_tpu.engine.request import Request, RequestState, SamplingParams
from radixmesh_tpu.models.llama import refuse_unsupported
from radixmesh_tpu.obs.trace_plane import get_recorder
from radixmesh_tpu.utils.logging import get_logger

__all__ = [
    "HandoffPacket",
    "PrefillWorker",
    "DecodeWorker",
    "IciHandoff",
    "pack_handoff",
    "unpack_handoff",
]


@dataclass
class HandoffPacket:
    """Everything a decode node needs to continue a prefilled request."""

    prompt: np.ndarray  # int32 [n]
    first_token: int  # sampled from the prefill logits
    kv: np.ndarray | jax.Array  # [2, L, n - kv_start, Hkv, D]
    sampling: SamplingParams = field(default_factory=SamplingParams)
    rid: int = -1
    submit_time: float = 0.0
    first_token_time: float = 0.0
    # KV covers prompt[kv_start:]; >0 when the sender knows the receiver
    # already caches the first kv_start tokens (tail-only shipping).
    kv_start: int = 0
    # Per-(token, head) scales when ``kv`` is an int8-quantized payload
    # ([2, L, n - kv_start, Hkv]); int8 + scales is 4x smaller on the wire
    # than the dequantized f32 a plain gather would ship.
    kv_scale: np.ndarray | jax.Array | None = None
    # The prefill leg won the tracing coin flip: the decode side follows
    # this bit instead of flipping its own, so under fractional sampling
    # a traced request's timeline spans BOTH nodes or neither — never an
    # orphan half.
    traced: bool = False
    # Cross-node stitching (PR 9): the prefill leg's 64-bit trace id.
    # The decode side ADOPTS it (instead of minting a node-local id), so
    # the stitched export shows pack → receive → kv_write → decode as
    # ONE timeline. 0 on packets from pre-stitching senders — the
    # receiver then falls back to the PR 2 behavior (fresh id, traced
    # bit only).
    trace_id: int = 0
    # Streamed handoff (cache/kv_transfer.py handoff lane): when
    # ``chunk_of`` > 0 this packet carries tokens
    # ``prompt[kv_start : kv_start + kv.shape[2])`` of a ``chunk_of``-way
    # split — the prefill side transmits completed chunks while later
    # gathers are still materializing, and the decode side stages each
    # chunk onto its devices as it lands (placement overlaps the rest of
    # the wire receive). ``chunk_seq`` orders them; the request admits
    # when all ``chunk_of`` chunks have arrived.
    chunk_seq: int = -1
    chunk_of: int = 0


@dataclass
class _StagedKV:
    """One staged block of handoff KV: covers layers
    ``[layer0, layer0 + kv.shape[1])`` and prompt tokens
    ``[tok0, tok0 + kv.shape[2])``. Whole legacy packets are the
    degenerate single block."""

    kv: object  # np.ndarray | jax.Array, token-major [2, nL, n, H, D]
    scale: object | None  # [2, nL, n, H] when quantized
    layer0: int
    tok0: int

    @property
    def n_tokens(self) -> int:
        return int(self.kv.shape[2])


class PrefillWorker(Engine):
    """A PREFILL-role node: runs prompt prefill with local radix-cache
    reuse, then hands the request off instead of decoding it.

    Subclasses :class:`Engine` so admission, prefix reuse, publish/lock
    bookkeeping, and eviction are shared with the collocated path; the only
    divergence is that a request's life here ends at its first token.
    """

    def prefill_handoff(
        self,
        prompt: Sequence[int],
        sampling: SamplingParams | None = None,
        skip_prefix: int = 0,
        device_kv: bool = False,
    ) -> HandoffPacket:
        """Prefill ``prompt`` and return its handoff packet. ``skip_prefix``
        omits the first N tokens' KV from the packet — use when the target
        decode node is known to cache them (page-aligned; see
        :meth:`DecodeWorker.cached_prefix_len`). With ``device_kv`` the
        packet's KV stays a ``jax.Array`` for the ICI path
        (:class:`IciHandoff`) — no device→host copy."""
        refuse_unsupported(self.cfg, disaggregated=True)
        req = self.add_request(prompt, sampling)
        self._admit()
        if req.state is not RequestState.RUNNING:
            # Leave no residue: a stale QUEUED request would be admitted by
            # the next call and occupy a batch row forever (this worker
            # never decodes requests it didn't just prefill).
            self.waiting.remove(req)
            raise RuntimeError("prefill pool exhausted; could not admit request")
        # Gather before release: release publishes the page-aligned prefix
        # to the tree but frees the tail partial page.
        tr = req.trace
        t_pack = time.monotonic() if tr is not None else 0.0
        kv, kv_scale = self.pool.gather_raw(req.token_slots[skip_prefix:])
        if not device_kv:
            kv = np.asarray(kv)
            kv_scale = None if kv_scale is None else np.asarray(kv_scale)
        if tr is not None:
            tr.add(
                "disagg_handoff_pack", t_pack,
                time.monotonic() - t_pack, cat="disagg",
                kv_tokens=int(len(req.token_slots) - skip_prefix),
                skip_prefix=int(skip_prefix),
            )
        pkt = HandoffPacket(
            prompt=req.prompt,
            first_token=req.output_tokens[0],
            kv=kv,
            sampling=req.sampling,
            rid=req.rid,
            submit_time=req.submit_time,
            first_token_time=req.first_token_time,
            kv_start=skip_prefix,
            kv_scale=kv_scale,
            traced=req.trace is not None,
            trace_id=req.trace.trace_id if req.trace is not None else 0,
        )
        req.state = RequestState.FINISHED
        self._release(req)
        return pkt

    def prefill_handoff_stream(
        self,
        prompt: Sequence[int],
        sampling: SamplingParams | None = None,
        *,
        send,
        chunk_tokens: int = 1024,
        skip_prefix: int = 0,
        plane=None,
    ) -> int:
        """Prefill ``prompt`` and STREAM its handoff as ``chunk_of``
        chunked packets through ``send(bytes)`` instead of one monolithic
        packet: each chunk's device gather is dispatched here (engine
        thread) and its materialization + pack + send run on the KV
        plane's handoff lane (``plane.submit_task``) — so chunk i's wire
        transmit overlaps chunk i+1's device→host gather, and the decode
        side starts placing early chunks while late ones are still in
        flight. With ``plane=None`` the pipeline degrades to an inline
        loop (same packets, no overlap). Returns the number of chunks
        sent/queued."""
        req = self.add_request(prompt, sampling)
        self._admit()
        if req.state is not RequestState.RUNNING:
            self.waiting.remove(req)
            raise RuntimeError("prefill pool exhausted; could not admit request")
        tr = req.trace
        n = len(req.token_slots)
        spans = [
            (lo, min(n, lo + chunk_tokens))
            for lo in range(skip_prefix, n, chunk_tokens)
        ]
        if not spans:
            # Fully tail-skipped handoff: the receiver caches the whole
            # prompt. Ship ONE empty-KV chunk — the request (and its
            # first token) must still arrive, exactly like the
            # monolithic path's empty-KV packet.
            spans = [(skip_prefix, skip_prefix)]
        chunk_of = len(spans)
        for seq, (lo, hi) in enumerate(spans):
            t_pack = time.monotonic()
            # Gather dispatched against the CURRENT pool buffer — the
            # release below only returns slots to the allocator; nothing
            # rewrites them before a later engine-thread scatter, which
            # the device sequences after this gather.
            kv, kv_scale = self.pool.gather_raw(req.token_slots[lo:hi])

            def _ship(kv=kv, kv_scale=kv_scale, lo=lo, seq=seq, t0=t_pack):
                pkt = HandoffPacket(
                    prompt=req.prompt,
                    first_token=req.output_tokens[0],
                    kv=np.asarray(kv),
                    sampling=req.sampling,
                    rid=req.rid,
                    submit_time=req.submit_time,
                    first_token_time=req.first_token_time,
                    kv_start=lo,
                    kv_scale=None if kv_scale is None else np.asarray(kv_scale),
                    traced=req.trace is not None,
                    trace_id=(
                        req.trace.trace_id if req.trace is not None else 0
                    ),
                    chunk_seq=seq,
                    chunk_of=chunk_of,
                )
                send(pack_handoff(pkt))
                if plane is not None:
                    plane.note_handoff(
                        pkt.kv.shape[2], self.pool, time.monotonic() - t0
                    )

            if plane is not None:
                plane.submit_task(_ship)
            else:
                _ship()
        if tr is not None:
            tr.add(
                "disagg_handoff_pack", time.monotonic(), 0.0, cat="disagg",
                kv_tokens=int(n - skip_prefix), skip_prefix=int(skip_prefix),
                chunks=chunk_of, streamed=True,
            )
        req.state = RequestState.FINISHED
        self._release(req)
        return chunk_of


class DecodeWorker:
    """A DECODE-role node: receives handoff packets (directly or via a
    :class:`Communicator`), writes the shipped KV pages into its own pool,
    and drives continuous-batching decode via the wrapped :class:`Engine`.

    Transport callbacks land on reader threads; the engine is
    single-threaded, so packets queue under a lock and :meth:`step` drains
    them on the scheduler thread.
    """

    def __init__(
        self,
        engine: Engine,
        comm: Communicator | None = None,
        plane=None,
        stage_layers: int = 0,
    ):
        refuse_unsupported(engine.cfg, disaggregated=True)
        self.engine = engine
        self.log = get_logger("disagg.decode")
        self._pending: list[tuple[Request, list[_StagedKV], int]] = []
        self._lock = threading.Lock()
        self.dropped = 0  # tail-only handoffs whose advertised prefix vanished
        # Staged placement (cache/kv_transfer.py handoff lane): with
        # ``stage_layers`` > 0, an incoming DCN packet's KV is split into
        # layer-blocks and each block's host→device transfer starts ON
        # THE TRANSPORT READER THREAD — placement overlaps both the
        # remaining wire receive and the engine's queue-drain latency,
        # and _admit_one's per-block scatters interleave with later
        # blocks' transfers instead of waiting for the whole packet.
        self.plane = plane
        self.stage_layers = int(stage_layers)
        # Streamed-handoff reassembly: (rid, origin submit stamp) →
        # {"t": first-seen, "parts": {seq: (packet, staged block)}}.
        # rids are per-PROCESS counters, so the submit stamp keeps two
        # prefill nodes' rid=N streams apart; stale partial streams (a
        # sender that died mid-handoff) expire so their staged device
        # arrays can't pin HBM forever.
        self._chunks: dict[tuple, dict] = {}
        self.chunk_ttl_s = 120.0
        self._comm = comm
        if comm is not None:
            comm.register_rcv_callback(self._on_packet)

    # -- ingestion ------------------------------------------------------

    def _on_packet(self, data: bytes) -> None:
        pkt = unpack_handoff(data)
        if pkt.chunk_of > 0:
            self._submit_chunk(pkt)
        else:
            self.submit(pkt)

    def _stage_blocks(self, pkt: HandoffPacket) -> list[_StagedKV]:
        """Split a packet into staged blocks. Device arrays (ICI path)
        and unstaged configs pass through as one block; DCN numpy
        payloads stage per layer-block when enabled."""
        kv, scale = pkt.kv, pkt.kv_scale
        if (
            self.stage_layers <= 0
            or isinstance(kv, jax.Array)
            or kv.shape[1] <= self.stage_layers
        ):
            return [_StagedKV(kv, scale, 0, int(pkt.kv_start))]
        t0 = time.monotonic()
        blocks = []
        for l0 in range(0, kv.shape[1], self.stage_layers):
            l1 = min(kv.shape[1], l0 + self.stage_layers)
            blocks.append(
                _StagedKV(
                    jnp.asarray(kv[:, l0:l1]),  # H2D starts now
                    None if scale is None else jnp.asarray(scale[:, l0:l1]),
                    l0,
                    int(pkt.kv_start),
                )
            )
        if self.plane is not None:
            self.plane.note_handoff(
                int(kv.shape[2]), self.engine.pool, time.monotonic() - t0
            )
        return blocks

    def _make_request(self, pkt: HandoffPacket) -> Request:
        # Same admission bound Engine.add_request enforces: a prompt longer
        # than this node's max_seq_len would overflow its page table
        # mid-admission, after state was already mutated.
        if not (0 < len(pkt.prompt) < self.engine.max_seq_len):
            raise ValueError(
                f"prompt length {len(pkt.prompt)} out of range for decode "
                f"engine (max_seq_len={self.engine.max_seq_len})"
            )
        req = Request(prompt=np.asarray(pkt.prompt, np.int32), sampling=pkt.sampling)
        req.output_tokens = [int(pkt.first_token)]
        req.submit_time = pkt.submit_time or time.monotonic()
        req.first_token_time = pkt.first_token_time or time.monotonic()
        # The decode-side leg of the flight, gated on the PACKET's traced
        # bit (not a fresh coin flip — see HandoffPacket.traced), tied
        # back to the prefill side by the handoff rid on the receive span.
        if pkt.traced:
            # force=True: the prefill node already flipped the coin —
            # re-flipping here would orphan half the cross-node timelines
            # at fractional sampling rates. The packet's trace id (PR 9)
            # is ADOPTED so both legs stitch into one timeline; packets
            # from pre-stitching senders carry 0 and get a fresh id.
            req.trace = get_recorder().trace(
                f"req:{req.rid}",
                force=True,
                trace_id=pkt.trace_id or None,
                node=self.engine.name,
            )
        if req.trace is not None:
            req.trace.add(
                "disagg_handoff_receive", time.monotonic(), 0.0,
                cat="disagg", handoff_rid=int(pkt.rid),
                kv_start=int(pkt.kv_start),
            )
        return req

    def submit(self, pkt: HandoffPacket) -> Request:
        req = self._make_request(pkt)
        blocks = self._stage_blocks(pkt)
        with self._lock:
            # KV stays whatever it arrived as: np.ndarray off the wire
            # (DCN path, unstaged), jax.Array off a ppermute (ICI path)
            # or a staged layer-block transfer.
            self._pending.append((req, blocks, int(pkt.kv_start)))
        return req

    def _submit_chunk(self, pkt: HandoffPacket) -> Request | None:
        """One chunk of a streamed handoff: stage its placement NOW
        (reader thread — overlapping the chunks still on the wire) and
        admit the request when the set completes. Returns the Request on
        completion, None while chunks are outstanding."""
        t0 = time.monotonic()
        staged = _StagedKV(
            jnp.asarray(pkt.kv) if isinstance(pkt.kv, np.ndarray) else pkt.kv,
            None if pkt.kv_scale is None else jnp.asarray(pkt.kv_scale),
            0,
            int(pkt.kv_start),
        )
        if self.plane is not None:
            self.plane.note_handoff(
                staged.n_tokens, self.engine.pool, time.monotonic() - t0
            )
        key = (int(pkt.rid), float(pkt.submit_time))
        now = time.monotonic()
        with self._lock:
            self._prune_stale_chunks_locked(now)
            entry = self._chunks.setdefault(key, {"t": now, "parts": {}})
            got = entry["parts"]
            got[int(pkt.chunk_seq)] = (pkt, staged)
            if len(got) < pkt.chunk_of:
                return None
            del self._chunks[key]
        ordered = [got[i] for i in sorted(got)]
        first_pkt = ordered[0][0]
        req = self._make_request(first_pkt)
        blocks = [st for _, st in ordered]
        with self._lock:
            self._pending.append((req, blocks, int(first_pkt.kv_start)))
        return req

    def cached_prefix_len(self, prompt: Sequence[int]) -> int:
        """How many leading tokens of ``prompt`` this node already caches
        (page-aligned, capped like admission reuse) — the safe
        ``skip_prefix`` for a tail-only handoff of this prompt."""
        eng = self.engine
        prompt = np.asarray(prompt, np.int32)
        match = eng.tree.match_prefix(prompt)
        return min(
            match.length, (len(prompt) - 1) // eng.page_size * eng.page_size
        )

    # -- scheduling -----------------------------------------------------

    def step(self) -> None:
        self._admit_pending()
        self.engine.step()

    def has_work(self) -> bool:
        with self._lock:
            if self._pending:
                return True
        return self.engine.has_work()

    def run_until_drained(self, max_steps: int = 100_000) -> None:
        for _ in range(max_steps):
            if not self.has_work():
                return
            self.step()
        raise RuntimeError("step budget exhausted with work remaining")

    def _prune_stale_chunks_locked(self, now: float) -> None:
        """Expire abandoned partial streams (dead sender mid-handoff) so
        their staged device arrays can't pin HBM forever. Caller holds
        the lock. Runs from BOTH the chunk receive path and the per-step
        scheduler drain — a stream stranded when chunked traffic stops
        must still expire."""
        for stale in [
            k for k, e in self._chunks.items()
            if now - e["t"] > self.chunk_ttl_s
        ]:
            self.log.error("dropping stale partial handoff stream %s", stale)
            del self._chunks[stale]

    def _admit_pending(self) -> None:
        with self._lock:
            if self._chunks:
                self._prune_stale_chunks_locked(time.monotonic())
            pending, self._pending = self._pending, []
        for i, (req, blocks, kv_start) in enumerate(pending):
            if not self._admit_one(req, blocks, kv_start):
                # Re-queue the failed packet AND everything after it —
                # admission stops at the first failure (row/pool pressure),
                # it must not drop the rest of the drained batch.
                with self._lock:
                    self._pending[:0] = pending[i:]
                return

    def _admit_one(
        self,
        req: Request,
        blocks: list[_StagedKV],
        kv_start: int,
    ) -> bool:
        eng = self.engine
        row = eng._free_row()
        if row < 0:
            return False
        n = len(req.prompt)
        # Local radix-cache check: a token-identical prefix already in this
        # node's pool is bitwise-reusable (same model, deterministic
        # prefill), so only the uncached tail of the shipped KV is written.
        acquired = eng._acquire_prompt_slots(req)
        if acquired is None:
            return False
        reuse, prefix_slots, own = acquired
        if reuse < kv_start:
            # Tail-only packet, but the cached prefix it relied on is gone
            # (evicted between advertisement and arrival). The KV for
            # [reuse, kv_start) exists nowhere on this node — the request
            # cannot run; drop it loudly rather than decode garbage.
            eng.tree.dec_lock_ref(req.lock_node)
            req.lock_node = None
            eng.pool.free(own)
            req.own_slots = np.empty(0, dtype=np.int32)
            req.state = RequestState.FINISHED
            self.log.error(
                "dropping handoff rid=%d: packet omits KV for [%d, %d) but "
                "local cache only covers %d tokens",
                req.rid, 0, kv_start, reuse,
            )
            self.dropped += 1
            return True  # consumed (not re-queued)
        n_new = n - reuse
        tr = req.trace
        t_write = time.monotonic() if tr is not None else 0.0
        # One pool scatter per staged block: a layer-block's write can
        # run while the NEXT block's host→device transfer (started on the
        # reader thread) is still streaming; a token-chunk block landed
        # (and started placing) before its successors even hit the wire.
        for b in blocks:
            lo = max(reuse, b.tok0)
            hi = min(n, b.tok0 + b.n_tokens)
            if hi <= lo:
                continue  # fully covered by local reuse
            sl, sh = lo - b.tok0, hi - b.tok0
            tail = self._colocate(jnp.asarray(b.kv[:, :, sl:sh]))
            scale = b.scale
            if scale is not None:
                scale = scale[:, :, sl:sh]
                if isinstance(scale, jax.Array):
                    scale = self._colocate(scale)
            dst = own[lo - reuse : hi - reuse]
            if scale is not None and eng.pool.quant is None:
                # Quantized sender, full-precision receiver: dequantize.
                deq = tail.astype(jnp.float32) * jnp.asarray(
                    scale, jnp.float32
                )[..., None]
                eng.pool.write_block(dst, deq, b.layer0)
            else:
                # Quantized end-to-end stores the shipped ints verbatim;
                # a quantized receiver of a float packet quantizes on
                # store; plain pools cast + store (write_block dispatch).
                eng.pool.write_block(dst, tail, b.layer0, scales=scale)

        req.kv_len = n
        req.token_slots = np.concatenate([prefix_slots, own[:n_new]])
        req.own_slots = own
        if tr is not None:
            tr.add(
                "disagg_kv_write", t_write,
                time.monotonic() - t_write, cat="disagg",
                kv_tokens=int(n_new), reused_tokens=int(reuse),
                blocks=len(blocks),
            )
        eng._install_running(req, row, reuse)
        return True

    def _colocate(self, arr: jax.Array) -> jax.Array:
        """Re-place an incoming device array onto this engine's pool
        devices. An ICI-moved block lives on the transfer mesh (which can
        span both workers' slices); the pool scatter needs its inputs on
        the pool's own device set — on TPU this ``device_put`` is the
        final placement hop onto the decode slice."""
        pool_sharding = self.engine.pool.kv.sharding
        if arr.sharding.device_set == pool_sharding.device_set:
            return arr
        from jax.sharding import NamedSharding, PartitionSpec

        if isinstance(pool_sharding, NamedSharding):
            target = NamedSharding(pool_sharding.mesh, PartitionSpec())
        else:
            target = next(iter(pool_sharding.device_set))
        return jax.device_put(arr, target)


class IciHandoff:
    """Prefill→decode KV movement over the ICI mesh (VERDICT round-2 weak
    #5: ``make_kv_page_transfer`` existed but the actual handoff always
    serialized through host bytes).

    When the prefill and decode workers share one TPU slice, a handoff
    packet's KV block rides a jitted ``ppermute``
    (``parallel/kv_transfer.py``) from the prefill rank's shard to the
    decode rank's shard — no JSON, no host RAM, XLA free to overlap the
    transfer with in-flight compute. The bytes path (:func:`pack_handoff`)
    remains the cross-slice/DCN plane; callers pick per SURVEY §5's split
    (collectives intra-slice, framed transport across).

    Shapes under jit are static, so token counts bucket to power-of-two
    page blocks (SURVEY §7 hard part (b)) — one compile per bucket, the
    engine's own discipline.
    """

    def __init__(
        self,
        mesh,
        axis_name: str,
        src_rank: int,
        dst_rank: int,
        page_size: int = 16,
    ):
        from radixmesh_tpu.parallel.kv_transfer import make_kv_page_transfer

        self.mesh = mesh
        self.axis = axis_name
        self.src = src_rank
        self.dst = dst_rank
        self.page_size = page_size
        self.n_ranks = mesh.shape[axis_name]
        if not (0 <= src_rank < self.n_ranks and 0 <= dst_rank < self.n_ranks):
            raise ValueError(
                f"ranks ({src_rank}->{dst_rank}) outside axis "
                f"{axis_name} of size {self.n_ranks}"
            )
        self._transfer = make_kv_page_transfer(
            mesh, axis_name, [(src_rank, dst_rank)]
        )
        from jax.sharding import NamedSharding, PartitionSpec

        src = src_rank
        n_ranks = self.n_ranks

        def build(padded):
            block = jnp.zeros((n_ranks, *padded.shape), padded.dtype)
            return block.at[src].set(padded)

        # jit with an output sharding: XLA materializes the block
        # PER-SHARD on its owning devices (src shard = payload, others =
        # zeros) instead of the eager path's full replicated array on one
        # device followed by a reshard — that spike is n_ranks x the KV
        # block, exactly what this class exists to avoid.
        self._build_block = jax.jit(
            build,
            out_shardings=NamedSharding(mesh, PartitionSpec(axis_name)),
        )

    def _blocked(self, arr: jax.Array) -> tuple[jax.Array, int]:
        """Pad the token axis (index 2 of ``[2, L, n, ...]``) to a
        power-of-two page block and add the leading rank axis, sharded
        over the transfer axis with the payload on ``src``."""
        n = arr.shape[2]
        # Same pow2 bucketing discipline as the engine's compile buckets.
        n_b = _pow2_at_least(max(n, 1), floor=self.page_size)
        pad = [(0, 0)] * arr.ndim
        pad[2] = (0, n_b - n)
        padded = jnp.pad(arr, pad)
        # The payload may be committed to the prefill worker's submesh;
        # place it on the transfer mesh so the sharded build can consume
        # it. Per-device footprint stays one block (the eager version
        # held n_ranks blocks on a single device).
        from jax.sharding import NamedSharding, PartitionSpec

        padded = jax.device_put(
            padded, NamedSharding(self.mesh, PartitionSpec())
        )
        return self._build_block(padded), n

    def move(self, pkt: HandoffPacket) -> HandoffPacket:
        """Return the packet with its KV (and scales) relocated to the
        decode rank's shard via ``ppermute``."""
        import dataclasses

        kv = pkt.kv if isinstance(pkt.kv, jax.Array) else jnp.asarray(pkt.kv)
        block, n = self._blocked(kv)
        moved = self._transfer(block)[self.dst, :, :, :n]
        scale = pkt.kv_scale
        if scale is not None:
            sblock, _ = self._blocked(
                scale if isinstance(scale, jax.Array) else jnp.asarray(scale)
            )
            scale = self._transfer(sblock)[self.dst, :, :, :n]
        return dataclasses.replace(pkt, kv=moved, kv_scale=scale)


# ----------------------------------------------------------------------
# wire format (DCN path)
# ----------------------------------------------------------------------

_HEADER_LEN_BYTES = 4


def pack_handoff(pkt: HandoffPacket) -> bytes:
    """``[4-byte header length][JSON header][raw KV bytes]`` — rides any
    length-framed :class:`Communicator` unchanged."""
    kv = np.asarray(pkt.kv)
    scale = None if pkt.kv_scale is None else np.asarray(pkt.kv_scale, np.float32)
    header = json.dumps(
        {
            "prompt": np.asarray(pkt.prompt).tolist(),
            "first_token": int(pkt.first_token),
            "rid": pkt.rid,
            "submit_time": pkt.submit_time,
            "first_token_time": pkt.first_token_time,
            "kv_shape": list(kv.shape),
            "kv_dtype": jnp.dtype(kv.dtype).name,
            "kv_start": int(pkt.kv_start),
            "traced": bool(pkt.traced),
            "trace_id": int(pkt.trace_id),
            "chunk_seq": int(pkt.chunk_seq),
            "chunk_of": int(pkt.chunk_of),
            "scale_shape": None if scale is None else list(scale.shape),
            "sampling": {
                "temperature": pkt.sampling.temperature,
                "top_p": pkt.sampling.top_p,
                "top_k": pkt.sampling.top_k,
                "max_new_tokens": pkt.sampling.max_new_tokens,
                "stop_token_ids": list(pkt.sampling.stop_token_ids),
            },
        }
    ).encode()
    parts = [len(header).to_bytes(_HEADER_LEN_BYTES, "big"), header, kv.tobytes()]
    if scale is not None:
        parts.append(scale.tobytes())
    return b"".join(parts)


def unpack_handoff(data: bytes) -> HandoffPacket:
    hlen = int.from_bytes(data[:_HEADER_LEN_BYTES], "big")
    h = json.loads(data[_HEADER_LEN_BYTES : _HEADER_LEN_BYTES + hlen])
    kv_dtype = jnp.dtype(h["kv_dtype"])
    n_kv = int(np.prod(h["kv_shape"])) * kv_dtype.itemsize
    body = data[_HEADER_LEN_BYTES + hlen :]
    kv = np.frombuffer(body[:n_kv], dtype=kv_dtype).reshape(h["kv_shape"])
    scale = None
    if h.get("scale_shape"):
        scale = np.frombuffer(body[n_kv:], dtype=np.float32).reshape(
            h["scale_shape"]
        )
    s = h["sampling"]
    return HandoffPacket(
        prompt=np.asarray(h["prompt"], np.int32),
        first_token=h["first_token"],
        kv=kv,
        sampling=SamplingParams(
            temperature=s["temperature"],
            top_p=s["top_p"],
            top_k=s.get("top_k", 0),  # absent in pre-top-k packets
            max_new_tokens=s["max_new_tokens"],
            stop_token_ids=tuple(s["stop_token_ids"]),
        ),
        rid=h["rid"],
        submit_time=h["submit_time"],
        first_token_time=h["first_token_time"],
        kv_start=h.get("kv_start", 0),
        kv_scale=scale,
        traced=bool(h.get("traced", False)),  # absent in pre-tracing packets
        trace_id=int(h.get("trace_id", 0)),  # absent in pre-stitching packets
        chunk_seq=int(h.get("chunk_seq", -1)),  # absent in pre-stream packets
        chunk_of=int(h.get("chunk_of", 0)),
    )
