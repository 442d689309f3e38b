"""Seeded random request storms against the serving engine.

The scenario tests in ``test_engine.py`` exercise one feature at a time;
production serving interleaves admission waves, mid-flight cancellation,
pool-pressure preemption, eviction, and mixed sampling configs. These
storms drive random schedules of all of them on a deliberately small pool
and then check the invariants any schedule must preserve:

- the engine drains (every request reaches FINISHED);
- uncancelled requests emit exactly their budget (or stop early only via
  their own stop tokens);
- slot accounting balances at the end: free + tree-referenced + scratch
  page == pool size, and the tree references only live slots.
"""

import numpy as np
import pytest

from radixmesh_tpu.engine import SamplingParams
from tests.test_engine import PAGE, make_engine, model  # noqa: F401


@pytest.mark.parametrize("seed", [2, 8, 21])
def test_request_storm_drains_and_balances(model, seed):
    cfg, params = model
    rng = np.random.default_rng(seed)
    eng = make_engine(
        model,
        num_slots=128,  # tight: forces eviction + preemption under load
        max_batch=3,
        spec_decode_tokens=3 if seed % 2 else 0,
        kv_quant="int8" if seed % 3 == 2 else None,
    )
    live: list = []
    done: list = []
    for _ in range(60):
        roll = rng.random()
        if roll < 0.35 and len(live) < 10:
            n = int(rng.integers(3, 24))
            prompt = rng.integers(1, cfg.vocab_size, n).tolist()
            temp = 0.0 if rng.random() < 0.7 else 0.8
            sp = SamplingParams(
                temperature=temp, max_new_tokens=int(rng.integers(2, 12))
            )
            live.append(eng.add_request(prompt, sp))
        elif roll < 0.45 and live:
            victim = live[int(rng.integers(0, len(live)))]
            eng.cancel(victim.rid)  # queued, running, or already finished
        elif eng.has_work():
            eng.step()
        # Retire finished requests from the live set.
        still = []
        for r in live:
            (done if r.state.value == "finished" else still).append(r)
        live = still

    while eng.has_work():
        eng.step()
    done.extend(live)

    for r in done:
        assert r.state.value == "finished", r
        if not r.cancelled:
            assert len(r.output_tokens) == r.sampling.max_new_tokens, (
                seed, r.rid, len(r.output_tokens), r.sampling.max_new_tokens,
            )
        assert all(0 <= t < cfg.vocab_size for t in r.output_tokens)

    # Slot accounting: everything not referenced by the tree (plus the
    # reserved scratch page) is back in the allocator.
    tree_tokens = eng.tree.total_size()
    assert eng.pool.free_slots + tree_tokens + PAGE == eng.pool.num_slots, (
        eng.pool.free_slots, tree_tokens,
    )
    # And every tree-referenced slot is genuinely allocated.
    for node in eng.tree._all_nodes():
        if node is not eng.tree.root and node.value is not None:
            assert eng.pool.allocator.is_allocated(
                np.asarray(node.value)
            ).all()
