"""Scheduler / cache: the share of the window's prompt tokens whose pages the
prefix trie served, ``prefix_hit_tokens / prompt_tokens_total`` from the
engine's counters (a shared 192-token prefix is 12 whole pages of 16; only
the suffix is prefilled). Nothing to read where no admission hit the trie."""
UNIT = "%"


def reduce(trace, counters, spans, shapes):
    prompts = counters.get("prompt_tokens_total")
    if not prompts or not counters.get("prefix_hit_tokens"):
        return None
    return 100.0 * counters["prefix_hit_tokens"] / prompts
