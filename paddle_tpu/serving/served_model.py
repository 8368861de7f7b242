"""The served-model protocol: what a causal LM hands ``GenerationEngine``.

The engine owns slots, pages, scheduling, spans and the two caches; it
contains no model. A model that can be served implements
``served_model()`` returning a :class:`ServedModel`:

- ``params(model)``: the live weights as a jax pytree ``{..., "layers":
  [{...}, ...]}`` — top-level arrays plus one dict per layer. The flat
  ``{dotted_name: array}`` form of the same tree (:func:`flatten_params`)
  is the wire shape ``swap_weights`` streams;
- ``embed(params, tokens, pos) -> x``: ``[rows, W]`` ids at global
  positions ``pos`` to the residual stream ``[rows, W, hidden]``;
- ``block(p, x, pos, attend, state, valid) -> (x, state)``: ONE layer.
  ``attend(q, k, v)`` is the engine's own attention: it writes ``k``/``v``
  (``[rows, W, kv_heads, head_dim]``) through the page table into the K/V
  arenas and returns the causal context of ``q`` (``[rows, W, heads,
  head_dim]``) against the pages — fused Pallas kernel or composed gather,
  the engine decides. ``state`` is the layer's recurrent state for the
  program's rows, or ``None``: a stateless model (GPT-2) always gets and
  returns ``None``; a model that declares ``state_spec`` gets ``None`` in a
  prompt's FIRST prefill call (a fresh sequence: start from zero and return
  the rows' FINAL state) and its slot arenas in a decode round (advance one
  step, return them updated). A model whose ``resumes_state`` is true gets,
  in a prompt's LATER prefill calls, the state its previous call returned
  (go on from it: the chunks of a prompt longer than the largest bucket),
  and is told which of the two it holds by ``step=`` (true: the slot arenas
  of a round; false: a row's own state, or ``None``) — ``block(p, x, pos,
  attend, state, valid, step=...)``; the engine installs the LAST call's
  state in the slot's row. A state model that does not resume (Falcon-H1:
  its chunked scan starts from zero) never sees a state in a prefill, and
  the engine refuses it a prompt longer than its largest bucket. ``valid``
  (``[rows, W]`` bool; a stateless model ignores it) marks the window
  positions that hold a real token: the recurrence must not advance on the
  others (a padded prefill bucket, an idle decode row);
- ``head(params, x) -> logits``: final norm and output head;
- THE STREAM BETWEEN BLOCKS is ``x`` and nothing else, but its trailing
  width is the model's: what must flow from layer to layer beside the
  residual stream rides as further COLUMNS of ``x`` — ``Xing4Served``'s four
  residual rows (``4 x hidden``), ``Zaya1Served``'s router representation
  (the last ``router_hidden_size`` columns: layer ``l``'s router reads layer
  ``l - 1``'s; ``embed`` starts them at zero, every block reads and rewrites
  them, ``head`` reads the first ``hidden`` alone). A wider ``x`` and not a
  pytree, because the engine's own reads of ``x`` are position-wise
  (``_last_real``, the carried step's ``x[:, W:]``) and never touch the last
  axis: no line of the engine knows of it. It is per token and per call,
  never cached;
- ``state_spec``: ``None``, or ``{name: (per-slot shape, dtype)}`` — the
  slot-indexed arenas the engine keeps per layer beside the paged K/V;
- ``cache_spec``: what ONE token leaves in a layer's paged cache — a dict
  whose format is described where it is parsed, ``paged_kv.CacheLayout``
  (``cache_layout`` hands a model's out), and which nothing else reads. What
  ``block`` is handed follows the kind. ``None`` (GPT-2, Falcon-H1):
  ``attend(q, k, v)`` as above. ``"latent"``: ``attend(q_lat, q_rope, row)``
  with ``q_lat`` ``[rows, W, heads, dv]``, ``q_rope`` ``[rows, W, heads, d -
  dv]`` and the window's own cache rows ``row`` ``[rows, W, d]``; the engine
  writes ``row`` through the page table and returns each head's
  softmax-weighted sum of the cached rows' first ``dv`` columns, ``[rows, W,
  heads, dv]`` (the absorbed form: the model carries it through its value
  up-projection). With an ``"index"`` group the ``attend`` of a layer that
  owns an indexer takes ``index=(qI, wI, kI)`` (index queries ``[rows, W, hi,
  di]``, their float32 weights ``[rows, W, hi]``, the window's own index keys
  ``[rows, W, di]``): the engine writes ``kI``, scores every visible key
  (``sum_j wI_j relu(qI_j . kI)``), takes each token's EXACT top-``k`` and
  attends those keys alone; a ``"shared"`` layer's takes no ``index`` and
  attends the set the last owner selected, which the window program keeps.
  ``"kv_by_layer"``: ``attend(q, k, v)``, the K/V form; the query's own shape
  says how many heads the layer has — ``num_heads`` is not read. Where the
  spec declares its layers' kinds (``"layers"``) each layer gets what ITS
  kind keeps: an ``attend`` built for it if it pages (``attend.kind`` is
  ``"full"`` or ``"window"`` — what a block needs to pick its RoPE or its
  sizes; a window layer's sees the keys ``i - n < j <= i``, a latent one's
  at its own row's widths), else ``None``; its own ``state`` if it keeps a
  row, else ``None``; both for ``"full+state"`` (``Zaya1ForCausalLM``: an
  attention whose keys are mixed by causal convolutions before they are
  cached keeps the conv's tail), neither for ``"none"`` (a position-wise
  layer: ``NemotronHForCausalLM``'s experts). ``{"kind": "none"}`` (Brumby):
  ``attend=None`` in every layer, no page table in the programs, and
  ``max_seq_len`` bounds positions only;
- ``program_counters``: ``None``, or the names of int32 scalars a block may
  hand back as a THIRD result (``(x, state, {name: scalar})``, ``None`` from
  a layer that has none). The window program sums them over its layers and
  returns them beside the tokens; the worker adds them to its counters at
  the sync it makes anyway;
- ``token_counters``: ``{counter: n}`` — every REAL token a window program
  runs through the blocks adds ``n`` to ``counter``, counted by the worker
  on the host where it dispatches the program (it knows the chunk's tokens
  and the round's live rows there; nothing is read back). What a model's
  own path does a fixed number of times a token, whatever the engine knows
  of it (``Xing4Served``: the residual path's mixes, two a layer);
- ``carries_rounds`` (derived, never set): whether the prefill program of
  the engine's largest bucket also runs the running sequences' decode step
  (``generation._build_window_step``, ``carry`` > 0 of its one ``step``
  body: "the carried step"). The rule, from three declarations and nothing
  else: the cache's kernel takes each row's own range of pages
  (``cache_spec`` of kind ``"latent"`` or ``"kv_by_layer"``), and what
  recurs, if anything does, RESUMES (no ``state_spec``, or
  ``resumes_state``). Then a chunk's row and a round's rows are just ``C +
  S`` tokens to everything position-wise in ``block``, and only what keeps
  memory tells them apart, each written once: ``attend`` (the builder's
  ``land`` and ``call`` split the row) and, in a ``"state"`` layer, the
  recurrence (or, in a ``"full+state"`` layer, BOTH: ``attend`` and a pair) —
  ``block`` is handed ``state`` as a :class:`Carried` PAIR (the
  prompt's own row from its previous chunk, or ``None``, and the slot
  arenas; ``step=False``), runs what recurs through :func:`recur`, which
  splits the window at the chunk's last token — the chunk from the row's
  state, the round's tokens one step each through the arenas, in place —
  and returns the pair ``(the chunk's final row, the arenas)``
  (``NemotronHServed``). A block that resumes already speaks both
  conventions (``step=``); the pair is the two in one program. Who stays
  out: Falcon-H1 and GPT-2 (``cache_spec`` ``None``: ``pt_paged_attention``
  walks every page of every slot; Falcon-H1's scan does not resume either,
  and GPT-2 inherits the carried step through this property once it moves
  onto the ranged kernel's layout, ROADMAP S2) and Brumby (nothing paged: it
  resumes, but no kernel of its takes a round's rows beside a chunk's:
  ROADMAP S16). For a state model that resumes and does not carry the engine
  sends a round of its own BETWEEN two chunks of a prompt
  (``GenerationEngine._round_between``); behind a call that carried one it
  sends none.

A block names the PARTS of its work (``observability.trace.parts``: ``norm``,
``attn_proj``, ``mlp``, and ``router`` / ``experts`` / ``mixer`` where it has
them) by decorating its helpers, residual adds included; the engine names
``embed``, ``cache_write`` (the ``attend`` it hands a block), ``attention``
and ``head`` itself. A device trace then says where a window program's time
goes in the model's own words (``tools/program_parts.py``), and
``tests/test_step_parts.py`` holds every served model to it.

What a cache kind cannot use of what assumes that a cache is pages of K/V
which, once written, stay (the prefix trie, speculative verify, KV-page
export/install, the warm tier) the engine refuses in words, from one table by
feature: ``paged_kv.CacheLayout.refuses`` (``docs/serving.md``).
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

from ..observability.trace.parts import part
from .paged_kv import CacheLayout

__all__ = ["ServedModel", "GPTServed", "Carried", "recur", "flatten_params",
           "nest_params"]


class ServedModel:
    """Base of the protocol; see the module docstring."""

    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    vocab_size: int
    max_positions: int
    attn_scale: float
    # None: the only cache is the paged K/V
    state_spec: Optional[Dict[str, Tuple[Tuple[int, ...], Any]]] = None
    # None: a token leaves K and V of [num_kv_heads, head_dim] in a layer;
    # else a dict in the format ``paged_kv.CacheLayout`` describes
    cache_spec: Optional[Dict[str, Any]] = None
    # None: the window programs hand back tokens and logprobs alone
    program_counters: Optional[Tuple[str, ...]] = None
    # counters every real token of a dispatched program adds to, by how much
    token_counters: Dict[str, int] = {}
    # True: ``block`` goes on from a state a previous prefill chunk returned
    # (and takes ``step=`` to tell that from the slot arenas of a round)
    resumes_state: bool = False

    @property
    def carries_rounds(self) -> bool:
        """Whether a prefill call of the largest bucket carries the running
        sequences' decode step (module docstring)."""
        return CacheLayout.ranges(self.cache_spec) and (
            self.state_spec is None or self.resumes_state)

    def cache_layout(self, page_len: int) -> CacheLayout:
        """What ``cache_spec`` and ``state_spec`` come to at pages of
        ``page_len`` tokens (``paged_kv.CacheLayout``: the format's reader)."""
        return CacheLayout.parse(self.cache_spec, self.state_spec,
                                 self.num_layers, page_len,
                                 self.num_kv_heads, self.head_dim)

    def params(self, model) -> Dict[str, Any]:
        raise NotImplementedError

    def embed(self, params, tokens, pos):
        raise NotImplementedError

    def block(self, p, x, pos, attend, state, valid):
        raise NotImplementedError

    def head(self, params, x):
        raise NotImplementedError


class Carried(NamedTuple):
    """A state-keeping layer's ``state`` in a program that carries a round
    (``ServedModel.carries_rounds``): the window is the chunk's ``W`` tokens,
    then one token for each slot of the arenas."""
    chunk: Any   # the prompt's own row from its previous chunk; None: zero
    round: Any   # the slot arenas, advanced one step in place
    W: int


def recur(run, state, step, *seqs):
    """What RECURS in a state layer, under whichever convention the program
    has: ``run(state, step, *seqs) -> (y, state)`` over ``seqs`` of ``[rows,
    W, ...]``. A row's own state (or ``None``) or, with ``step``, the slot
    arenas: ``run`` as it is. A :class:`Carried` pair: the chunk's ``[:, :W]``
    from the row's state, the round's ``[:, W:]`` turned to ``[R, 1, ...]``
    one step through the arenas; ``y`` joined as the block handed the window
    in, and the pair ``(the chunk's final row, the arenas)`` for a state —
    the split written once, as ``_build_window_step``'s ``call`` is for
    ``attend``."""
    if not isinstance(state, Carried):
        return run(state, step, *seqs)
    import jax.numpy as jnp

    W = state.W
    y, row = run(state.chunk, False, *(s[:, :W] for s in seqs))
    r_y, arenas = run(state.round, True,
                      *(jnp.swapaxes(s[:, W:], 0, 1) for s in seqs))
    return jnp.concatenate([y, jnp.swapaxes(r_y, 0, 1)], 1), (row, arenas)


def flatten_params(tree) -> Dict[str, Any]:
    """Flatten an engine param pytree to ``{dotted_name: array}`` — the
    wire shape the post-training weight service streams (stable names,
    no nesting to re-derive on the far side)."""
    flat = {k: v for k, v in tree.items() if k != "layers"}
    for i, L in enumerate(tree["layers"]):
        for k, v in L.items():
            flat[f"layers.{i}.{k}"] = v
    return flat


def nest_params(flat) -> Dict[str, Any]:
    """Inverse of :func:`flatten_params`."""
    tree: Dict[str, Any] = {"layers": []}
    layers: Dict[int, Dict[str, Any]] = {}
    for name, v in flat.items():
        if name.startswith("layers."):
            _, idx, key = name.split(".", 2)
            layers.setdefault(int(idx), {})[key] = v
        else:
            tree[name] = v
    for i in sorted(layers):
        if i != len(tree["layers"]):
            raise ValueError(f"non-contiguous layer index {i}")
        tree["layers"].append(layers[i])
    return tree


class GPTServed(ServedModel):
    """GPT-2 on the seam: token + learned position embedding, pre-LN
    blocks with biases (fused QKV, tanh-GELU MLP), tied head. Stateless."""

    def __init__(self, cfg):
        self.num_layers = cfg.num_hidden_layers
        self.num_heads = self.num_kv_heads = cfg.num_attention_heads
        self.head_dim = cfg.hidden_size // cfg.num_attention_heads
        self.vocab_size = cfg.vocab_size
        self.max_positions = cfg.max_position_embeddings
        self.attn_scale = 1.0 / math.sqrt(self.head_dim)
        self._eps = cfg.layer_norm_epsilon

    def params(self, model):
        """Read the live weights of a ``GPTForCausalLM`` (the programs
        close over nothing — set_state_dict + a new engine picks up new
        weights)."""
        g = model.gpt

        def a(t):
            return t.data

        return {
            "embed": a(g.embed_tokens.weight),          # [vocab, h]
            "pos": a(g.embed_positions.weight),         # [P, h]
            "lnf_w": a(g.ln_f.weight), "lnf_b": a(g.ln_f.bias),
            "layers": [
                {"ln1_w": a(L.ln_1.weight), "ln1_b": a(L.ln_1.bias),
                 "qkv_w": a(L.attn.qkv_proj.weight),
                 "qkv_b": a(L.attn.qkv_proj.bias),
                 "out_w": a(L.attn.out_proj.weight),
                 "out_b": a(L.attn.out_proj.bias),
                 "ln2_w": a(L.ln_2.weight), "ln2_b": a(L.ln_2.bias),
                 "fc_in_w": a(L.fc_in.weight), "fc_in_b": a(L.fc_in.bias),
                 "fc_out_w": a(L.fc_out.weight),
                 "fc_out_b": a(L.fc_out.bias)}
                for L in g.layers],
        }

    @part("norm")
    def _ln(self, x, w, b):
        import jax
        import jax.numpy as jnp

        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + self._eps) * w + b

    def embed(self, params, tokens, pos):
        import jax.numpy as jnp

        pos_idx = jnp.minimum(pos, params["pos"].shape[0] - 1)
        return params["embed"][tokens] + params["pos"][pos_idx]

    # the parts of the block (``observability.trace.parts``) sit on helpers,
    # so that ``block``, which a window program traces once a layer, stays
    # short

    @part("attn_proj")
    def _qkv(self, p, h1):
        S, W = h1.shape[0], h1.shape[1]
        qkv = (h1 @ p["qkv_w"] + p["qkv_b"]).reshape(
            S, W, 3, self.num_heads, self.head_dim)
        return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

    @part("attn_proj")
    def _attn_out(self, p, x, ctx):
        ctx = ctx.reshape(ctx.shape[0], ctx.shape[1], -1)
        return x + (ctx @ p["out_w"] + p["out_b"])

    @part("mlp")
    def _mlp(self, p, x, h2):
        import jax

        m = jax.nn.gelu(h2 @ p["fc_in_w"] + p["fc_in_b"], approximate=True)
        return x + (m @ p["fc_out_w"] + p["fc_out_b"])

    def block(self, p, x, pos, attend, state, valid):
        x = self._attn_out(p, x, attend(*self._qkv(
            p, self._ln(x, p["ln1_w"], p["ln1_b"]))))
        return self._mlp(p, x, self._ln(x, p["ln2_w"], p["ln2_b"])), None

    def head(self, params, x):
        xf = self._ln(x, params["lnf_w"], params["lnf_b"])
        return xf @ params["embed"].T
