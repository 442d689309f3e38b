"""Qwen2 (the dense family, ``models/qwen2.py``) at toy widths on the CPU: the
rehearsal twin of the two ``qwen2-7b`` cells through the conformance suite
(``tests/model_conformance.py``). The dense layer resumes from the pool's
rows alone and every engine option serves it, so the cases that need a
state, a tail or a refusal skip here with that reason.

The dense family has no file under ``benchmarks/reference/``: its plain
reference is ``prefill_forward`` over the WHOLE sequence in float32 — one
launch, no cache, no pool, no decode step — which is what the chunked
prefill, the paged decode steps and a prefix served from the tree each have
to equal."""

import jax.numpy as jnp
import pytest

from radixmesh_tpu.models.llama import prefill_forward, unsupported_reason
from tests.model_conformance import *  # noqa: F401,F403
from tests.model_conformance import (  # noqa: F401  (collected once, here)
    ModelSpec,
    test_every_configuration_of_the_manifest_has_a_spec,
)

pytestmark = pytest.mark.quick


def whole_sequence(params, cfg, seq, logits_from):
    tokens = jnp.asarray([seq], jnp.int32)
    empty = jnp.zeros((cfg.n_layers, 1, 0, cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
    logits = prefill_forward(
        params, cfg, tokens, jnp.arange(len(seq))[None], empty, empty,
        jnp.zeros((1,), jnp.int32))[0]
    return logits[0, logits_from:]


SPEC = ModelSpec(
    preset="qwen2-tiny", seed=57, reference=whole_sequence,
    engine=dict(page_size=16, num_slots=4096, max_batch=4, max_seq_len=512,
                prefill_chunk=64),
    # 7: inside one page; 48: a page multiple; 150: chunks of 64 over ten pages
    lengths=(7, 48, 150),
)


def test_the_dense_layer_is_served_with_every_option(model):
    """What the suite's refusal cases skip on: ``unsupported_reason`` names
    nothing for the dense layer, whatever is asked."""
    cfg = model[0]
    assert unsupported_reason(
        cfg, weight_quant="int8", kv_quant="int8", spec_decode_tokens=3,
        host_cache_slots=64, mesh_cache=True, stream_publish_tokens=8,
        disaggregated=True, hf_checkpoint=True) is None
    eng = SPEC.make_engine(model)
    assert not eng._resumes and eng.pool.state is None and eng.pool.window is None
    assert eng.pool.kv.shape == cfg.kv_pool_shape(4096) == (2, 2, 2, 4096, 32)
