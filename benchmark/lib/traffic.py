"""The one general traffic generator. A traffic mix is DATA (the ``traffic``
group of ``benchmark/workloads/<cell>.json``); this file is the only code
that reads it, so a later cell is a new data file and nothing else.

Two kinds:

``packed_documents`` (training): an endless stream of documents whose
lengths are log-normal (heavy-tailed) and whose tokens are Zipf-distributed
over the vocabulary, joined by an end-of-document token and cut into
``[batch, seq]`` rows by a host iterator that runs while the device trains.

``open_loop`` (serving): requests sent on a schedule whatever the server
does. Every seed gets the SAME inter-arrival gaps, prompt lengths and output
lengths — the quantiles of the stated distributions at ``(i + 0.5) / n`` —
in the same order (the mix's ``order_seed`` fixes it), and differs only in
the token ids, so two seeds offer the same work at the same instants. Optional ``shared_prefix`` (a share of
the requests starts with one of a few common prefixes) and ``bursts``
(on/off periods at the same mean rate) are parameters, not code paths a
later cell has to add.
"""
from __future__ import annotations

import math
import statistics
import time
from typing import Callable, Dict, Iterator, List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


def _rng(seed: int, stream: int) -> np.random.Generator:
    # --seed may exceed 32 signed bits; SeedSequence takes any non-negative
    # whole number, and the stream index keeps draws for different purposes
    # independent of each other
    return np.random.default_rng(np.random.SeedSequence([abs(int(seed)),
                                                         stream]))


def lognormal_quantiles(n: int, spec: Dict) -> np.ndarray:
    """``n`` whole lengths: the log-normal ``spec`` (median, sigma) at the
    mid-quantiles, clipped to ``[min, max]``. Deterministic, seed-free."""
    q = (np.arange(n) + 0.5) / n
    z = np.array([_NORMAL.inv_cdf(float(x)) for x in q])
    vals = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def exponential_quantiles(n: int, rate: float) -> np.ndarray:
    """``n`` inter-arrival gaps of a Poisson process of ``rate`` per second,
    at the mid-quantiles, rescaled so that they sum to exactly n / rate."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    return gaps * (n / rate) / gaps.sum()


# -- training ------------------------------------------------------------------

def packed_documents(traffic: Dict, vocab_size: int,
                     seed: int) -> Iterator[np.ndarray]:
    """Endless iterator of int64 ``[batch, seq]`` arrays."""
    batch, seq = int(traffic["batch"]), int(traffic["seq"])
    doc = traffic["doc_len"]
    eos = int(traffic.get("eos_token", 2))
    rng = _rng(seed, 1)
    need = batch * seq
    carry = np.empty(0, dtype=np.int64)
    log_v = math.log(vocab_size)
    mu = math.log(float(doc["median"]))
    while True:
        parts = [carry]
        have = carry.size
        while have < need:
            n_docs = max(4, 2 * (need - have) // int(doc["median"]))
            lens = np.clip(np.rint(rng.lognormal(mu, float(doc["sigma"]),
                                                 n_docs)),
                           doc["min"], doc["max"]).astype(np.int64)
            total = int(lens.sum())
            # Zipf (exponent 1) by inverse CDF: rank = V**u is log-uniform,
            # so p(rank) ~ 1/rank; token id = rank - 1 (low ids frequent)
            toks = np.minimum(np.exp(rng.random(total) * log_v).astype(
                np.int64), vocab_size) - 1
            toks[np.cumsum(lens) - 1] = eos  # each document ends in EOS
            parts.append(toks)
            have += total
        stream = np.concatenate(parts)
        carry = stream[need:]
        yield stream[:need].reshape(batch, seq)


# -- serving -------------------------------------------------------------------

class Request:
    __slots__ = ("index", "due", "prompt", "max_new", "t_send", "stamps",
                 "done", "error", "result")

    def __init__(self, index: int, due: float, prompt: np.ndarray,
                 max_new: int):
        self.index = index
        self.due = due          # seconds after the window opens
        self.prompt = prompt
        self.max_new = max_new
        self.t_send: Optional[float] = None   # clock time it was sent
        self.stamps: List[float] = []         # clock time of each token
        self.done: Optional[float] = None     # clock time it completed
        self.error: Optional[str] = None
        self.result = None


def open_loop_schedule(traffic: Dict, vocab_size: int, seed: int,
                       seconds: float) -> List[Request]:
    """The requests due in ``[0, seconds)``, in due order.

    The mix fixes ONE sequence of (gap, prompt length, output length): the
    quantiles of its distributions, paired and ordered by the mix's own
    ``order_seed``. ``seed`` draws the token ids (and, in the runner, the
    weights), nothing else: with a few tens of ten-second requests in a
    window, whatever the seed did to the ORDER (a shuffle, or only another
    entry point into the same cycle: both tried on the chip, PR 23) made a
    run's tokens per second and its latencies a property of its seed — 3 %
    between seeds where two runs of one seed differed by 0.3 %.
    """
    rate = float(traffic["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    order = int(traffic.get("order_seed", 0))
    gaps = exponential_quantiles(n, rate)
    p_lens = lognormal_quantiles(n, traffic["prompt_len"])
    o_lens = lognormal_quantiles(n, traffic["output_len"])
    _rng(order, 2).shuffle(gaps)
    _rng(order, 3).shuffle(p_lens)
    _rng(order, 4).shuffle(o_lens)
    # the last one is due as the window closes
    due = np.minimum(np.cumsum(gaps), seconds)
    bursts = traffic.get("bursts")
    if bursts:
        # the same mean rate, squeezed into the `on` part of each period:
        # arrivals are laid on the active-time axis, then spread out
        on, off = float(bursts["on_s"]), float(bursts["off_s"])
        active = due * on / (on + off)
        due = active + np.maximum(np.ceil(active / on - 1e-9) - 1, 0) * off
    due = np.minimum(due, np.nextafter(seconds, 0))
    tok = _rng(seed, 5)
    sp = traffic.get("shared_prefix") or {}
    share, ptoks = float(sp.get("share", 0)), int(sp.get("tokens", 0))
    prefixes = [tok.integers(0, vocab_size, ptoks, dtype=np.int64)
                for _ in range(int(sp.get("n_prefixes", 1)))] \
        if share > 0 and ptoks > 0 else []
    # exactly round(share * n) requests share a prefix, whichever the seed
    shared = np.zeros(n, dtype=bool)
    shared[:int(round(share * n))] = bool(prefixes)
    _rng(order, 6).shuffle(shared)
    pmax = int(traffic["prompt_len"]["max"])
    out = []
    for i in range(n):
        body = tok.integers(0, vocab_size, int(p_lens[i]), dtype=np.int64)
        if shared[i]:
            pre = prefixes[int(tok.integers(0, len(prefixes)))]
            body = np.concatenate([pre, body])[:pmax]
        out.append(Request(i, float(due[i]), body, int(o_lens[i])))
    return out


def run_open_loop(requests: List[Request], send: Callable[[Request], None],
                  now: Callable[[], float] = time.perf_counter,
                  sleep: Callable[[float], None] = time.sleep,
                  t0: Optional[float] = None) -> float:
    """Send each request when it is due, never earlier, whatever happened to
    the ones before (open loop). A request is timed from when it was DUE:
    ``t_send - (t0 + due)`` is how late the generator ran, and belongs to
    the generator, not the server. Returns ``t0``, the window's start."""
    t0 = now() if t0 is None else t0
    for r in requests:
        wait = t0 + r.due - now()
        if wait > 0:
            sleep(wait)
        r.t_send = now()
        send(r)
    return t0
