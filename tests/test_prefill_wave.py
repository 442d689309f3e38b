"""A planned prefill wave against its rows prefilled one at a time (PR 47).

One tiny configuration for each kind of row the served models have. A
wave of ragged rows over a cached document is prefilled in the launches
``plan_prefill`` draws for them — rows of one launch at different places
of their prompts, a tail in launches of its own, later levels without the
rows that have ended — under a cost that makes it split where the tiny
model's own (a launch is all fixed cost) would keep the box. The same
requests sent one at a time to a second engine give the same greedy
tokens, the same tree (what a later request matches, and where it would
resume: snapshots and window tails), and the counters add up to the
launches that were sent."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from radixmesh_tpu.engine.engine import Engine
from radixmesh_tpu.engine.request import SamplingParams
from radixmesh_tpu.models import get_config, init_params

pytestmark = pytest.mark.quick

PAGE, CHUNK, N_OUT = 16, 32, 4
KINDS = {
    "dense-gqa": "llama3-tiny",
    "window-experts": "mellum2-tiny",
    "latent": "glm-4.7-flash-tiny",
    "sparse-width-selects": "glm-5.2-tiny",
    "conv-state": "lfm2-tiny",
    "ssm-state-window-tail": "phi4flash-tiny",
    "window-tail-alone": "laguna-tiny",
}
DOC = 40  # cached: its two whole pages (a state model resumes at 32)
# new tokens past the pages the tree serves: one bucket (33-64), two levels
# of 32, five rows (a launch of four and one of one)
NEWS = (33, 40, 50, 64, 57)


@pytest.fixture(scope="module", params=list(KINDS))
def model(request):
    cfg = get_config(KINDS[request.param]).replace(dtype=jnp.float32)
    return cfg, init_params(cfg, jax.random.PRNGKey(47))


def make_engine(model):
    cfg, params = model
    eng = Engine(cfg, params, num_slots=4096, page_size=PAGE, max_batch=8,
                 max_seq_len=256, prefill_chunk=CHUNK)
    # A launch worth 8 tokens beside 2 a launch costs: every padded row and
    # every padded half chunk is worth a launch of its own.
    eng.prefill_cost = (8.0, 2.0)
    return eng


def tokens_of(n, seed):
    return np.random.default_rng([47, seed]).integers(1, 500, n).tolist()


def drain(eng):
    while eng.has_work():
        eng.step()


def tree_view(eng, req):
    """What a later request with this sequence would find: the rows the
    tree serves, and every place it could resume."""
    seq = np.asarray(list(req.prompt) + req.generated, np.int32)
    m = eng.tree.match_prefix(seq)
    return m.length, [pos for pos, _ in m.states]


def test_a_planned_wave_equals_its_rows_one_at_a_time(model):
    doc = tokens_of(DOC, seed=0)
    prompts = [doc + tokens_of(n - (DOC - 32), seed=n) for n in NEWS]
    sampling = SamplingParams(max_new_tokens=N_OUT)

    alone = make_engine(model)
    alone.add_request(doc + tokens_of(2, seed=1), SamplingParams(max_new_tokens=2))
    drain(alone)
    want = []
    for p in prompts:
        req = alone.add_request(p, sampling)
        drain(alone)
        want.append((req.prefix_len, req.generated, tree_view(alone, req)))

    eng = make_engine(model)
    eng.add_request(doc + tokens_of(2, seed=1), SamplingParams(max_new_tokens=2))
    drain(eng)
    sent = []
    forward = eng._forward_chunk

    def recorded(toks, *args, **kw):
        if eng.spans.innermost == "rm.prefill.group":
            sent.append(toks.shape)
        return forward(toks, *args, **kw)

    eng._forward_chunk = recorded
    before = dict(eng.prefill)
    reqs = [eng.add_request(p, sampling) for p in prompts]
    drain(eng)
    got = [(r.prefix_len, r.generated, tree_view(eng, r)) for r in reqs]
    assert got == want
    assert all(r.prefix_len == 32 and len(r.generated) == N_OUT for r in reqs)

    p = eng.prefill
    assert p["waves"] == before["waves"] + 1  # one sub-wave, one finalize
    assert p["rows"] - before["rows"] == len(NEWS)
    assert p["tokens_new"] - before["tokens_new"] == sum(NEWS)
    # the sum of B x C over the launches actually sent, and their number
    assert p["launches"] - before["launches"] == len(sent) > 2
    launched = p["tokens_launched"] - before["tokens_launched"]
    assert launched == sum(b * c for b, c in sent)
    # the box: 8 rows x 2 chunks of 32
    assert p["tokens_boxed"] - before["tokens_boxed"] == 8 * 2 * CHUNK
    assert sum(NEWS) <= launched < 8 * 2 * CHUNK
    # rows of several launches, and a launch of one row with a short chunk
    assert {b for b, _ in sent} >= {1, 4} and min(c for _, c in sent) < CHUNK
