"""A row's host cost follows its new tokens, not its context: the decode
advance appends a slot in place (``Request.append_slot``) and a publish
starts at the request's locked node (``RadixTree.insert_below``). The
engine side of it, on the toy model: same tokens out, same slots freed,
same tree as the root-walking publish left (the values below are what the
commit before this change produced for the same runs), and the counters
that say the mechanism engages. The tree side is the twin-tree test of
``tests/test_radix_tree.py``."""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from radixmesh_tpu.engine import Engine, SamplingParams
from radixmesh_tpu.engine.request import Request
from radixmesh_tpu.models.llama import ModelConfig, init_params

pytestmark = pytest.mark.quick


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.tiny().replace(dtype=jnp.float32)
    return cfg, init_params(cfg, jax.random.PRNGKey(7))


def make_engine(model, page, **kw):
    cfg, params = model
    kw.setdefault("num_slots", 40 * page)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 8 * page)
    return Engine(cfg, params, page_size=page, **kw)


def drain(eng):
    while eng.has_work():
        eng.step()


def digest(tokens):
    return len(tokens), zlib.crc32(np.asarray(tokens, np.int32).tobytes())


# What the root-walking publish (the parent commit) left after
# ``mixed_run`` below: each request's (tokens out, crc32 of them), then the
# pool and the tree.
RECORDED = {
    16: {
        "outputs": [(19, 4246388319), (19, 4058311469), (11, 1196357508),
                    (5, 2853059543), (5, 3527949929), (5, 692159001),
                    (5, 879668394)],
        "free_slots": 464, "tree_total": 160, "evictable": 160, "protected": 0,
        "cached_tokens": 352, "fingerprint": 783675625189309216, "nodes": 9,
    },
    128: {
        "outputs": [(131, 3240401494), (131, 123305361), (67, 4007674678),
                    (5, 1665573008), (33, 3771992020), (33, 776435189),
                    (33, 3513277288)],
        "free_slots": 4096, "tree_total": 896, "evictable": 896, "protected": 0,
        "cached_tokens": 2688, "fingerprint": 814499632506517084, "nodes": 6,
    },
}


def mixed_run(eng, page):
    """Three sessions on one document beside a cold prompt; one of them
    preempted and one cancelled mid-reply; then each comes back with its
    reply and a question."""
    rng = np.random.default_rng(11)

    def tok(n):
        return rng.integers(1, eng.cfg.vocab_size, n).tolist()

    doc = tok(3 * page + 5)
    first = [eng.add_request(doc + tok(page // 2 + i),
                             SamplingParams(max_new_tokens=page + 3))
             for i in range(3)]
    cold = eng.add_request(tok(page + 7), SamplingParams(max_new_tokens=5))
    while min(len(r.output_tokens) for r in first) < page // 2 + 2:
        eng.step()
    eng._settle()
    eng._preempt(first[1])  # starts over, from what it published
    assert first[1].token_slots.size == 0 and first[1]._slots.size == 0
    assert eng.cancel(first[2].rid)
    drain(eng)
    second = [eng.add_request(list(r.prompt) + r.output_tokens + tok(7 + i),
                              SamplingParams(max_new_tokens=page // 4 + 1))
              for i, r in enumerate(first)]
    drain(eng)
    return [*first, cold, *second]


@pytest.mark.parametrize("page", [16, 128])
def test_a_mixed_run_ends_where_the_root_walking_publish_ended(model, page):
    eng = make_engine(model, page)
    reqs = mixed_run(eng, page)
    want = RECORDED[page]
    assert [digest(r.output_tokens) for r in reqs] == want["outputs"]
    assert eng.stats.preemptions == 1 and reqs[2].cancelled
    tree = eng.tree
    got = {
        "free_slots": eng.pool.free_slots, "tree_total": tree.total_size(),
        "evictable": tree.evictable_size(), "protected": tree.protected_size(),
        "cached_tokens": eng.stats.cached_tokens,
        "fingerprint": tree.fingerprint,
        "nodes": sum(1 for _ in tree._all_nodes()),
    }
    assert got == {k: want[k] for k in got}
    # every usable slot is free or the tree's, none lost and none twice
    assert eng.pool.free_slots + tree.total_size() == eng.pool.num_slots - page
    # three turns on a long shared prefix and a preempted one's second
    # life among the publishes: they walked a fraction of their keys
    th = eng.tree_host
    assert th["publishes"] == 2 * len(reqs) + 2  # two a life, and one had two
    assert 0 < th["publish_walked_tokens"] < th["publish_key_tokens"] // 2


@pytest.mark.parametrize("page", [16, 128])
def test_a_decode_step_appends_the_rows_slot_in_place(model, page):
    eng = make_engine(model, page)
    prompt = np.random.default_rng(5).integers(1, eng.cfg.vocab_size, 2 * page + 3)
    req = eng.add_request(prompt.tolist(), SamplingParams(max_new_tokens=page + 2))
    eng.step()  # admission: the prompt installed, room for the reply reserved
    eng._settle()
    buf = req._slots
    assert len(buf) >= len(prompt) + page + 2
    seen = []
    while eng.has_work():
        eng.step()
        eng._settle()
        if req.row >= 0:  # still a live row
            assert req._slots is buf  # no reallocation between two steps
            slots = req.token_slots
            assert len(slots) == req.kv_len and np.shares_memory(slots, buf)
            seen.append(slots.copy())
    assert len(seen) > page // 2 and len(seen[-1]) > 3 * page  # a page was crossed
    for a, b in zip(seen, seen[1:]):  # what was appended stays as it was
        assert len(b) == len(a) + 1 and (b[: len(a)] == a).all()
    # what the tree holds are copies: the row's buffer is nobody else's
    for node in eng.tree._all_nodes():
        if node.value is not None and len(node.key):
            assert not np.shares_memory(np.asarray(node.value), buf)


@pytest.mark.parametrize("page", [16, 128])
def test_the_publish_counters_say_when_the_walk_starts_at_the_lock(model, page):
    eng = make_engine(model, page)
    rng = np.random.default_rng(9)
    doc = rng.integers(1, eng.cfg.vocab_size, 5 * page).tolist()

    def counters():
        th = eng.tree_host
        return th["publish_key_tokens"], th["publish_walked_tokens"]

    # a cold prompt: the whole key is new, the publish walks all of it
    cold = eng.add_request(doc + [3, 4, 5], SamplingParams(max_new_tokens=page))
    eng.step()
    eng._settle()
    assert cold.prefix_len == 0
    assert counters() == (len(cold.prompt), len(cold.prompt))
    drain(eng)
    key, walked = counters()
    # ... and its release only what it generated since (and the residue)
    # (the last token's row is never computed: a key ends one short)
    assert key == 2 * len(cold.prompt) + page - 1
    assert walked == len(cold.prompt) + page + 2

    # a turn on that long shared prefix: both publishes start at its lock
    turn = eng.add_request(doc + [3, 4, 5] + cold.output_tokens + [8, 9],
                           SamplingParams(max_new_tokens=4))
    drain(eng)
    assert turn.prefix_len == 6 * page
    key2, walked2 = counters()
    n = len(turn.prompt)
    assert key2 - key == n + n + 3
    assert walked2 - walked == (n - 6 * page) + (n + 3 - 6 * page)
    assert (walked2 - walked) * 10 < key2 - key


def test_token_slots_is_a_view_of_what_was_appended_and_no_more():
    req = Request(prompt=np.arange(4, dtype=np.int32))
    assert req.token_slots.size == 0
    given = np.arange(10, 14, dtype=np.int32)
    req.token_slots = given  # the buffer as it is: no spare capacity yet
    assert req._slots is given and req.token_slots.tolist() == [10, 11, 12, 13]
    req.append_slot(20)  # grows by doubling, and leaves `given` alone
    assert req._slots is not given and len(req._slots) == 8
    assert given.tolist() == [10, 11, 12, 13]
    buf = req._slots
    req.reserve_slots(3)  # fits: nothing moves
    assert req._slots is buf
    for s in (21, 22, 23):
        req.append_slot(s)
    assert req._slots is buf and len(req.token_slots) == 8
    req.append_slot(24)
    assert len(req._slots) == 16
    assert req.token_slots.tolist() == [10, 11, 12, 13, 20, 21, 22, 23, 24]
    req.reserve_slots(100)
    assert len(req._slots) == 109 and len(req.token_slots) == 9
    req.token_slots[2:4] = [7, 7]  # a reader may write what it sees
    assert req._slots[:4].tolist() == [10, 11, 7, 7]
    req.token_slots = np.empty(0, dtype=np.int32)
    assert req._slots.size == 0 and req.token_slots.size == 0
