"""KV pool: cache rows the window's launches did NOT read because they
were not selected, percent of the rows their contexts hold: 1 - delta
``rows_selected`` / delta ``rows_held`` of ``/debug/state``
``attn.sparse`` (decode and prefill launches together; host arithmetic on
the lengths, x layers): what dense latent attention would have read and
learned sparse attention skips, while the pool still holds every row."""
from benchmarks.harness.registry import load_module

_delta = load_module("layer_metrics", "dsa_index_hbm_share").sparse_delta


def read(ctx):
    held = selected = 0
    for site in ("decode", "prefill"):
        h, s = _delta(ctx, site, "rows_held"), _delta(ctx, site, "rows_selected")
        if h is None or s is None:
            return None
        held, selected = held + h, selected + s
    if held <= 0:
        return None
    return 100.0 * (1.0 - selected / held)
