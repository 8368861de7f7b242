"""Scheduler and cache: the keys the window's queries ATTENDED
(``attn_keys_selected_*_total``: what the indexer's exact top-k left, counted
on the device, summed over the layers) as a share of what a dense causal
attention would have scored (``attn_keys_decode_total`` +
``attn_keys_prefill_total``, once a layer): about ``index_topk`` over half the
mean context in range. 100 means nothing is selected away."""
UNIT = "%"


def reduce(trace, counters, spans, shapes):
    shape = shapes.get("dsa")
    dense = counters.get("attn_keys_decode_total", 0) + \
        counters.get("attn_keys_prefill_total", 0)
    if not shape or not dense:
        return None
    selected = counters.get("attn_keys_selected_decode_total", 0) + \
        counters.get("attn_keys_selected_prefill_total", 0)
    return 100.0 * selected / (dense * shape["layers"])
