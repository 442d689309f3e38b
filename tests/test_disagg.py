"""Disaggregated prefill/decode: KV handoff correctness vs the collocated
engine, wire round-trip over a Communicator, decode-side cache reuse, and
the ICI page-permute path on the 8-device virtual mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from radixmesh_tpu.comm.inproc import InprocCommunicator, InprocHub
from radixmesh_tpu.engine import Engine, SamplingParams
from radixmesh_tpu.engine.disagg import (
    DecodeWorker,
    PrefillWorker,
    pack_handoff,
    unpack_handoff,
)
from radixmesh_tpu.models.llama import ModelConfig, init_params

PAGE = 4


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.tiny().replace(dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def make_prefill(model, **kw):
    cfg, params = model
    kw.setdefault("num_slots", 512)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 128)
    return PrefillWorker(cfg, params, **kw)


def make_decode(model, comm=None, **kw):
    cfg, params = model
    kw.setdefault("num_slots", 512)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 128)
    return DecodeWorker(Engine(cfg, params, **kw), comm=comm)


def collocated_generate(model, prompts, n_new):
    cfg, params = model
    eng = Engine(cfg, params, num_slots=512, page_size=PAGE, max_batch=4,
                 max_seq_len=128)
    return eng.generate(prompts, SamplingParams(max_new_tokens=n_new))


class TestHandoff:
    def test_disagg_matches_collocated(self, model):
        rng = np.random.default_rng(11)
        prompts = [rng.integers(0, 64, size=n).tolist() for n in (9, 17, 5)]
        want = collocated_generate(model, prompts, 8)

        pw = make_prefill(model)
        dw = make_decode(model)
        reqs = [
            dw.submit(pw.prefill_handoff(p, SamplingParams(max_new_tokens=8)))
            for p in prompts
        ]
        dw.run_until_drained()
        got = [r.generated for r in reqs]
        assert got == want

    def test_wire_roundtrip(self, model):
        pw = make_prefill(model)
        pkt = pw.prefill_handoff([1, 2, 3, 4, 5, 6, 7], SamplingParams(max_new_tokens=4))
        pkt2 = unpack_handoff(pack_handoff(pkt))
        assert np.array_equal(pkt.prompt, pkt2.prompt)
        assert pkt.first_token == pkt2.first_token
        assert pkt.sampling == pkt2.sampling
        assert np.asarray(pkt.kv).dtype == np.asarray(pkt2.kv).dtype
        np.testing.assert_array_equal(np.asarray(pkt.kv), np.asarray(pkt2.kv))

    def test_handoff_over_communicator(self, model):
        InprocHub.reset_default()
        try:
            rx = InprocCommunicator("decode:0", None)
            tx = InprocCommunicator(None, "decode:0")
            dw = make_decode(model, comm=rx)
            pw = make_prefill(model)
            prompt = [3, 1, 4, 1, 5, 9, 2, 6]
            want = collocated_generate(model, [prompt], 6)[0]
            pkt = pw.prefill_handoff(prompt, SamplingParams(max_new_tokens=6))
            tx.send(pack_handoff(pkt))
            deadline = 100
            while not dw.has_work() and deadline:
                deadline -= 1
                import time

                time.sleep(0.01)
            assert dw.has_work(), "packet never arrived"
            dw.run_until_drained()
            req = dw.engine.stats
            assert req.finished == 1
        finally:
            InprocHub.reset_default()

    def test_decode_side_prefix_reuse(self, model):
        """Second handoff sharing a long prefix reuses the decode node's
        cached pages instead of rewriting shipped KV."""
        pw = make_prefill(model)
        dw = make_decode(model)
        base = list(range(1, 25))
        r1 = dw.submit(pw.prefill_handoff(base + [30], SamplingParams(max_new_tokens=3)))
        dw.run_until_drained()
        r2 = dw.submit(pw.prefill_handoff(base + [31], SamplingParams(max_new_tokens=3)))
        dw.run_until_drained()
        stats = dw.engine.stats
        assert stats.cached_tokens >= 24 // PAGE * PAGE
        # Both finished and generated the same as collocated.
        want = collocated_generate(model, [base + [30], base + [31]], 3)
        assert [r1.generated, r2.generated] == want

    def test_tail_only_handoff(self, model):
        """skip_prefix ships only the uncached tail's KV; generation is
        unchanged and the packet is smaller."""
        pw = make_prefill(model)
        dw = make_decode(model)
        base = list(range(1, 25))
        dw.submit(pw.prefill_handoff(base + [30], SamplingParams(max_new_tokens=3)))
        dw.run_until_drained()
        skip = dw.cached_prefix_len(base + [31])
        assert skip >= 24 // PAGE * PAGE
        full = pw.prefill_handoff(base + [31], SamplingParams(max_new_tokens=3))
        pkt = pw.prefill_handoff(
            base + [31], SamplingParams(max_new_tokens=3), skip_prefix=skip
        )
        assert np.asarray(pkt.kv).shape[2] == len(base) + 1 - skip
        assert len(pack_handoff(pkt)) < len(pack_handoff(full))
        r = dw.submit(pkt)
        dw.run_until_drained()
        want = collocated_generate(model, [base + [31]], 3)[0]
        assert r.generated == want
        assert dw.dropped == 0

    def test_tail_only_handoff_dropped_when_prefix_gone(self, model):
        """A tail-only packet whose advertised prefix was evicted is
        dropped loudly, not decoded from garbage."""
        pw = make_prefill(model)
        dw = make_decode(model)
        prompt = list(range(1, 20))
        pkt = pw.prefill_handoff(prompt, SamplingParams(max_new_tokens=3), skip_prefix=8)
        r = dw.submit(pkt)  # decode cache is empty: prefix never existed
        dw.run_until_drained()
        assert dw.dropped == 1
        assert r.state.value == "finished"
        assert dw.engine.stats.finished == 0  # dropped, not completed

    def test_prefill_side_prefix_reuse(self, model):
        """The prefill worker's own radix cache accelerates shared prompts."""
        pw = make_prefill(model)
        base = list(range(40, 70))
        pw.prefill_handoff(base + [1], SamplingParams(max_new_tokens=1))
        pw.prefill_handoff(base + [2], SamplingParams(max_new_tokens=1))
        assert pw.stats.cached_tokens >= len(base) // PAGE * PAGE


class TestIciTransfer:
    def test_page_permute(self):
        from radixmesh_tpu.parallel.kv_transfer import (
            make_kv_page_transfer,
            prefill_to_decode_perm,
        )
        from jax.sharding import Mesh

        devices = np.array(jax.devices()[:8])
        mesh = Mesh(devices, ("pd",))
        # 4 prefill ranks [0..3], 4 decode ranks [4..7].
        perm = prefill_to_decode_perm(4, 4)
        assert perm == [(0, 4), (1, 5), (2, 6), (3, 7)]
        transfer = make_kv_page_transfer(mesh, "pd", perm)
        # One page batch per rank: [8 shards * 2 pages, page=4, H=2, D=3]
        block = jnp.arange(8 * 2 * 4 * 2 * 3, dtype=jnp.float32).reshape(
            16, 4, 2, 3
        )
        out = np.asarray(transfer(block))
        src = np.asarray(block)
        for i in range(4):  # decode rank 4+i receives prefill rank i's shard
            np.testing.assert_array_equal(
                out[(4 + i) * 2 : (5 + i) * 2], src[i * 2 : (i + 1) * 2]
            )
        # Non-destination ranks (prefill side) hold zeros.
        np.testing.assert_array_equal(out[:8], np.zeros_like(out[:8]))

    def test_perm_validation(self):
        from radixmesh_tpu.parallel.kv_transfer import prefill_to_decode_perm

        assert prefill_to_decode_perm(2, 3) == [(0, 2), (1, 3)]
        with pytest.raises(ValueError):
            prefill_to_decode_perm(0, 2)
        # P > D cannot be one injective ppermute; must be rejected, not
        # deferred to an XLA error at trace time.
        with pytest.raises(ValueError):
            prefill_to_decode_perm(3, 2)


class TestQuantizedHandoff:
    """Int8 pools ship their exact stored representation (int8 + scales,
    4x smaller than dequantized f32) and the receiver stores it verbatim —
    no dequantize→requantize drift across the handoff."""

    def test_quant_to_quant_matches_collocated_quant(self, model):
        cfg, params = model
        rng = np.random.default_rng(12)
        prompts = [rng.integers(0, 64, size=n).tolist() for n in (9, 14)]
        ref = Engine(cfg, params, num_slots=512, page_size=PAGE, max_batch=4,
                     max_seq_len=128, kv_quant="int8")
        want = ref.generate(prompts, SamplingParams(max_new_tokens=8))

        pw = make_prefill(model, kv_quant="int8")
        dw = make_decode(model, kv_quant="int8")
        reqs = [
            dw.submit(pw.prefill_handoff(p, SamplingParams(max_new_tokens=8)))
            for p in prompts
        ]
        dw.run_until_drained()
        assert [r.generated for r in reqs] == want

    def test_quant_wire_roundtrip_preserves_ints_and_scales(self, model):
        pw = make_prefill(model, kv_quant="int8")
        pkt = pw.prefill_handoff(
            [1, 2, 3, 4, 5, 6, 7], SamplingParams(max_new_tokens=4)
        )
        assert np.asarray(pkt.kv).dtype == np.int8
        assert pkt.kv_scale is not None
        pkt2 = unpack_handoff(pack_handoff(pkt))
        np.testing.assert_array_equal(np.asarray(pkt.kv), np.asarray(pkt2.kv))
        np.testing.assert_array_equal(
            np.asarray(pkt.kv_scale), np.asarray(pkt2.kv_scale)
        )
        # int8 + f32 scales ≈ (1 + 4/D)/4 of the f32 payload a plain
        # gather would ship.
        kv_bytes = np.asarray(pkt.kv).nbytes
        assert np.asarray(pkt.kv_scale).nbytes * 4 <= kv_bytes  # D >= 16

    def test_quant_sender_fp_receiver(self, model):
        # Mixed deployment: the receiver dequantizes the shipped ints.
        pw = make_prefill(model, kv_quant="int8")
        dw = make_decode(model)
        req = dw.submit(
            pw.prefill_handoff([3, 1, 4, 1, 5, 9, 2, 6],
                               SamplingParams(max_new_tokens=6))
        )
        dw.run_until_drained()
        assert len(req.generated) == 6

    def test_fp_sender_quant_receiver(self, model):
        pw = make_prefill(model)
        dw = make_decode(model, kv_quant="int8")
        req = dw.submit(
            pw.prefill_handoff([2, 7, 1, 8, 2, 8], SamplingParams(max_new_tokens=6))
        )
        dw.run_until_drained()
        assert len(req.generated) == 6


class TestTopKHandoff:
    def test_top_k_survives_the_wire(self, model):
        pw = make_prefill(model)
        pkt = pw.prefill_handoff(
            [1, 2, 3, 4, 5],
            SamplingParams(temperature=1.3, top_k=1, max_new_tokens=4),
        )
        pkt2 = unpack_handoff(pack_handoff(pkt))
        assert pkt2.sampling.top_k == 1

    def test_disagg_top_k_one_matches_greedy(self, model):
        # k=1 at high temperature must stay greedy ACROSS the handoff.
        want = collocated_generate(model, [[7, 7, 2, 9, 1]], 6)
        pw, dw = make_prefill(model), make_decode(model)
        req = dw.submit(
            pw.prefill_handoff(
                [7, 7, 2, 9, 1],
                SamplingParams(temperature=1.3, top_k=1, max_new_tokens=6),
            )
        )
        dw.run_until_drained()
        assert req.generated == want[0]


class TestSpecOnDecodeWorker:
    def test_decode_worker_with_spec_matches_plain(self, model):
        """Speculative decoding on the decode side of a disaggregated
        deployment: greedy outputs must match a spec-off decode worker."""
        prompt = [5, 1, 5, 1, 5, 1, 5, 1]
        # Long enough for this model's greedy stream to repeat a bigram
        # (the n-gram drafter needs one; with these weights it first
        # happens at token 19).
        sampling = SamplingParams(max_new_tokens=24)
        pw = make_prefill(model)
        dw_plain = make_decode(model)
        want = dw_plain.submit(pw.prefill_handoff(prompt, sampling))
        dw_plain.run_until_drained()

        pw2 = make_prefill(model)
        dw_spec = make_decode(model, spec_decode_tokens=3)
        got = dw_spec.submit(pw2.prefill_handoff(prompt, sampling))
        dw_spec.run_until_drained()
        assert got.generated == want.generated
        # The equality must not be vacuous: speculation actually engaged.
        assert dw_spec.engine.stats.spec_proposed > 0


class TestIciHandoff:
    """The handoff's KV moving over the ICI plane (VERDICT round-2 weak
    #5): prefill gathers on device, a ppermute relocates the page block
    to the decode rank's shard, decode admits the jax.Array directly —
    host RAM and JSON never touched."""

    @pytest.fixture(scope="class")
    def mesh(self):
        from jax.sharding import Mesh

        return Mesh(np.asarray(jax.devices()[:4]), axis_names=("dp",))

    def test_ici_move_is_device_to_device_and_lossless(self, model, mesh):
        from radixmesh_tpu.engine.disagg import IciHandoff

        prompt = list(range(1, 23))
        pre = make_prefill(model)
        chan = IciHandoff(mesh, "dp", src_rank=0, dst_rank=2, page_size=PAGE)
        pkt = pre.prefill_handoff(
            prompt, SamplingParams(max_new_tokens=6), device_kv=True
        )
        assert isinstance(pkt.kv, jax.Array)  # no host copy on gather
        moved = chan.move(pkt)
        assert isinstance(moved.kv, jax.Array)  # still on device post-move
        np.testing.assert_array_equal(np.asarray(moved.kv), np.asarray(pkt.kv))

    def test_ici_handoff_end_to_end_tokens(self, model, mesh):
        from radixmesh_tpu.engine.disagg import IciHandoff

        prompt = list(range(30, 55))
        want = collocated_generate(model, [prompt], 6)[0]
        pre = make_prefill(model)
        dec = make_decode(model)
        chan = IciHandoff(mesh, "dp", src_rank=1, dst_rank=3, page_size=PAGE)
        pkt = chan.move(
            pre.prefill_handoff(
                prompt, SamplingParams(max_new_tokens=6), device_kv=True
            )
        )
        req = dec.submit(pkt)
        dec.run_until_drained()
        assert req.output_tokens == want

    def test_ici_rank_validation(self, mesh):
        from radixmesh_tpu.engine.disagg import IciHandoff

        with pytest.raises(ValueError, match="outside axis"):
            IciHandoff(mesh, "dp", src_rank=0, dst_rank=9)


@pytest.mark.quick
class TestStagedStreamedHandoff:
    """PR 4 handoff lane: layer-block staging on the receive thread and
    the chunk-streamed wire path must generate EXACTLY what the
    monolithic packet does."""

    def _decode_with_staging(self, model, stage_layers, **kw):
        cfg, params = model
        kw.setdefault("num_slots", 512)
        kw.setdefault("page_size", PAGE)
        kw.setdefault("max_batch", 4)
        kw.setdefault("max_seq_len", 128)
        return DecodeWorker(
            Engine(cfg, params, **kw), stage_layers=stage_layers
        )

    def test_layer_staged_packet_matches_monolithic(self, model):
        pw = make_prefill(model)
        prompt = list(range(1, 60))
        sp = SamplingParams(max_new_tokens=6)
        pkt = unpack_handoff(pack_handoff(pw.prefill_handoff(prompt, sp)))
        ref = make_decode(model)
        r0 = ref.submit(pkt)
        ref.run_until_drained()
        staged = self._decode_with_staging(model, stage_layers=1)
        r1 = staged.submit(pkt)
        staged.run_until_drained()
        assert r1.generated == r0.generated

    def test_streamed_chunks_match_monolithic(self, model):
        pw = make_prefill(model)
        prompt = list(range(1, 70))
        sp = SamplingParams(max_new_tokens=6)
        ref_pkt = pw.prefill_handoff(prompt, sp)
        ref = make_decode(model)
        r0 = ref.submit(ref_pkt)
        ref.run_until_drained()

        wire: list[bytes] = []
        n = pw.prefill_handoff_stream(
            prompt, sp, send=wire.append, chunk_tokens=16
        )
        assert n == len(wire) > 1
        dw = self._decode_with_staging(model, stage_layers=0)
        for frame in wire:
            dw._on_packet(frame)
        req = dw._pending[0][0]
        dw.run_until_drained()
        assert req.generated == r0.generated

    def test_streamed_chunks_tolerate_out_of_order_delivery(self, model):
        pw = make_prefill(model)
        prompt = list(range(1, 70))
        sp = SamplingParams(max_new_tokens=4)
        ref_pkt = pw.prefill_handoff(prompt, sp)
        ref = make_decode(model)
        r0 = ref.submit(ref_pkt)
        ref.run_until_drained()

        wire: list[bytes] = []
        pw.prefill_handoff_stream(prompt, sp, send=wire.append, chunk_tokens=16)
        dw = self._decode_with_staging(model, stage_layers=0)
        for frame in reversed(wire):  # reassembly must sort by chunk_seq
            dw._on_packet(frame)
        req = dw._pending[0][0]
        dw.run_until_drained()
        assert req.generated == r0.generated

    def test_streamed_through_plane_pipeline(self, model):
        """send runs on the plane worker (pipelined with later gathers);
        the wire content must be identical to the inline loop's."""
        from radixmesh_tpu.cache.kv_transfer import KVTransferPlane

        pw = make_prefill(model)
        prompt = list(range(1, 50))
        sp = SamplingParams(max_new_tokens=4)
        inline: list[bytes] = []
        pw.prefill_handoff_stream(prompt, sp, send=inline.append, chunk_tokens=16)
        plane = KVTransferPlane(name="handoff-test")
        try:
            piped: list[bytes] = []
            done = __import__("threading").Event()
            pw.prefill_handoff_stream(
                prompt, sp, send=piped.append, chunk_tokens=16, plane=plane
            )
            plane.submit_task(done.set)  # FIFO: fires after all sends
            assert done.wait(10)
            assert len(piped) == len(inline)
            # Same chunk_of/kv_start framing and (numerically) the same
            # payloads — the second serve recomputes the non-page-aligned
            # tail token through a different compile bucket, so the last
            # chunk matches to float tolerance rather than bitwise.
            for a, b in zip(piped, inline):
                pa, pb = unpack_handoff(a), unpack_handoff(b)
                assert pa.chunk_seq == pb.chunk_seq
                assert pa.chunk_of == pb.chunk_of
                assert pa.kv_start == pb.kv_start
                np.testing.assert_allclose(
                    np.asarray(pa.kv), np.asarray(pb.kv), rtol=1e-3, atol=1e-4
                )
        finally:
            plane.close()

    def test_fully_skipped_stream_still_delivers_request(self, model):
        """skip_prefix covering the whole prompt must still SHIP the
        request as one empty-KV chunk — the receiver then resolves it
        like any over-skipped packet (admit on sufficient local reuse,
        or drop LOUDLY), instead of the stream silently sending zero
        packets and losing the request forever."""
        pw = make_prefill(model)
        prompt = list(range(1, 41))
        sp = SamplingParams(max_new_tokens=4)
        dw = make_decode(model)
        wire: list[bytes] = []
        n = pw.prefill_handoff_stream(
            prompt, sp, send=wire.append, chunk_tokens=16,
            skip_prefix=len(prompt),
        )
        assert n == len(wire) == 1  # one empty-KV chunk, not zero packets
        dw._on_packet(wire[0])
        req = dw._pending[0][0]
        dw.run_until_drained()
        # Local reuse caps below the full prompt by design, so this
        # over-skipped handoff resolves as the DOCUMENTED loud drop —
        # observable and counted, not vanished.
        assert req.state.value == "finished"
        assert dw.dropped == 1
