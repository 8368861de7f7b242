"""Kernels: the latent rows the window kernel's tiles WALKED over the window
(``attn_rows_walked_window_total``: the rows of the blocks every grid step of
``pt_mla_window_attention`` DMAs) as a share of the rows inside its queries'
windows (``attn_rows_in_window_total``: the union of a tile's windows, once a
grid step) — the walk's page-granular amplification. 100 when the walk reads
nothing it masks; lower is better. A program that counts neither reads as
nothing."""
UNIT = "%"


def reduce(trace, counters, spans, shapes):
    inside = counters.get("attn_rows_in_window_total")
    if not inside:
        return None
    return 100.0 * counters.get("attn_rows_walked_window_total", 0) / inside
