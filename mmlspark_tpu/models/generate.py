"""Autoregressive generation with a KV cache: the product half of the
long-context LM stack.

The reference has no language model at all (SURVEY §2b headroom), but a
framework that advertises flash/ring-attention training must also produce
tokens.  Design is jit-once / static-shape throughout — the TPU decode
recipe:

  * **prefill**: one full forward over the (fixed-length) prompt writes
    every layer's K/V into a max_len-sized cache and yields the first
    sampled token.  Attention is the ordinary causal batched matmul for
    short prompts (XLA fuses it) and the pallas flash kernel from
    _PREFILL_FLASH_MIN tokens up — a long prompt must not materialize
    the O(P^2) score tensor the flash path exists to avoid.
  * **decode**: a `lax.scan` over step count; each step embeds ONE token,
    updates the caches via `lax.dynamic_update_slice` at a traced
    position, and attends the single query against the full cache under a
    global position mask.  Shapes never change, so the whole generation
    is one compiled program — no per-step dispatch, no retracing, no
    Python in the loop.
  * **sampling**: greedy (temperature 0) or temperature-scaled
    categorical over the top-k / top-p (nucleus) filtered distribution,
    decided at trace time (`filter_logits`).

On top of that per-length recipe sits the **decode engine**
(`DecodeEngine`): the serving path `TextGenerator` actually runs.  Three
compounding optimisations over the one-program-per-prompt-length design:

  * **length-bucketed prefill** — prompts are right-padded to a small set
    of buckets (next power of two, floored at `DEFAULT_MIN_BUCKET`), with
    per-row true-length position ids, attention visibility masks, and a
    per-row last-logit gather, so a ragged workload collapses from one
    compiled program *and one tiny batch per distinct length* into a
    handful of shared shape classes scoring full batches.
  * **cache-windowed decode** — generation runs in segments whose
    compiled scan attends only over a cache *prefix* rounded up to a
    chunk (`decode_segments`); the window grows as the write position
    crosses chunk boundaries, so steady-step bandwidth scales with cache
    occupancy instead of max_len.  Segment programs take the bucket and
    step offsets as traced scalars, so buckets whose windows coincide
    share one compiled segment.
  * **stop-token early exit** — a per-row done mask rides the scan (done
    rows freeze on their stop token) and the engine host-checks `done`
    between segments, so a batch whose rows have all stopped skips the
    remaining segments instead of always paying max_new_tokens steps.

Greedy tokens are exactly those of the per-length decoder (test-pinned
across bucket/window configurations): padding holes are masked to exact
zero weight and positions are per-row, so bucketing is pure layout.
Sampling keys fold in a stable per-row id — a row's draws depend only on
(seed, row id, step), never on how rows were grouped or batched.  Beam
search stays on the full-cache per-length path (windowing lands
sampler-first; see docs/performance.md).

This file is the engine and knows no model's layers.  It asks the model
for a DECODING, once, when the engine is built (`_decoding_for`): each
layer's state kind and empty state, which weights stay resident in the
compute dtype, and the calls its programs make (a prompt segment, the
head, a decode step at a shared or at per-row slots, the close of a
prompt, the entry and exit of a segment).  `TransformerDecoding`
(models/transformer_decoding.py) answers for TransformerLM, over the SAME
flax param tree training wrote, so any trained bundle generates without
re-exporting weights; `HybridDecoding` (models/hybrid_lm.py) for HybridLM.
A new architecture is a decoding class beside its layers, an entry in
`_DECODINGS` and a plain reference for the tests; `DecodeEngine` is not
edited.  The full-cache per-length decoder and beam search call
TransformerLM's whole-segment forward directly.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mmlspark_tpu.core.params import Param
from mmlspark_tpu.core.pipeline import Transformer
from mmlspark_tpu.models.bundle import load_bundle, save_bundle
from mmlspark_tpu.models.definitions import TransformerLM
from mmlspark_tpu.models.hybrid_lm import FIXED, WINDOW, HybridDecoding, HybridLM
from mmlspark_tpu.models.transformer_decoding import (TransformerDecoding,
                                                      forward_with_cache)
from mmlspark_tpu.observe import compiles
from mmlspark_tpu.observe.costmodel import capture_program_cost
from mmlspark_tpu.observe.spans import active_timings, span_on
from mmlspark_tpu.observe.telemetry import active_run
from mmlspark_tpu.observe.trace import trace_event, trace_span
from mmlspark_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, SEQ_AXIS
from mmlspark_tpu.parallel.partition import (
    DRAFT_KV_CACHE_SPEC,
    DRAFT_KV_SCALE_SPEC,
    KV_CACHE_SPEC,
    KV_SCALE_SPEC,
    SEQ_KV_CACHE_SPEC,
    SEQ_KV_SCALE_SPEC,
    shard_constraint,
    use_mesh,
)

NEG_INF = -1e30

# Speculative-decoding RNG streams: disjoint fold_in offsets keep draft
# draws, acceptance coins, and residual/bonus draws off the non-spec
# per-step streams (fold_in(row_key, step), step < max_new_tokens).  A
# row's speculative draws depend only on (its key, round, position) —
# never on batch composition — matching the engine's sampling contract.
_SPEC_DRAFT_STREAM = 1 << 20
_SPEC_COIN_STREAM = 2 << 20
_SPEC_FIX_STREAM = 3 << 20


def _kv_hint(cache_spec, scale_spec):
    """A K/V state leaf's sharding hint: rank-4 (B, W, H, D) payloads take
    `cache_spec`, the rank-3 (B, W, H) scales of an int8 cache
    `scale_spec`.  Off-mesh the hint is identity (shard_constraint
    degrades), so every decode path stays single-device-portable.  A
    head-folded payload (B, W, H*D) is rank 3 too and would take the
    scales' spec: harmless, because windows are head-folded off-mesh
    only (`TransformerDecoding.folds`), where no spec applies."""
    def hint(c: jax.Array) -> jax.Array:
        if c.ndim == 4:
            return shard_constraint(c, cache_spec)
        if c.ndim == 3:
            return shard_constraint(c, scale_spec)
        return c
    return hint


# heads on 'model'
_hint_kv = _kv_hint(KV_CACHE_SPEC, KV_SCALE_SPEC)
# the DRAFT model's cache: batch on 'data', heads replicated (a latency-sized
# draft rarely has a head count the model axis divides, and its forward is a
# rounding error next to the target's)
_hint_draft_kv = _kv_hint(DRAFT_KV_CACHE_SPEC, DRAFT_KV_SCALE_SPEC)
# a SEQ-SHARDED cache: the WINDOW axis splits over 'seq', so each chip holds
# a contiguous slab of slots — the long-context layout where one chip's HBM
# no longer bounds the window.  Heads stay unsharded (the seq engine path
# refuses model>1 meshes)
_hint_seq_kv = _kv_hint(SEQ_KV_CACHE_SPEC, SEQ_KV_SCALE_SPEC)


# The full-cache per-length programs (`make_generate_fn`, beam search) and
# the draft of a speculating engine take TransformerLM only.  `DecodeEngine`
# takes every architecture that has a decoding: the model's own statement
# of what the engine's programs ask of it (the module docstring).
_FULL_CACHE_ARCHITECTURES = (TransformerLM,)
_DECODINGS = {TransformerLM: TransformerDecoding, HybridLM: HybridDecoding}


def _check_generatable(module, accepted=_FULL_CACHE_ARCHITECTURES,
                       what: str = "generate() and beam search") -> None:
    if not isinstance(module, accepted):
        raise ValueError(
            f"{what} decode "
            f"{' and '.join(a.__name__ for a in accepted)} models, got "
            f"{type(module).__name__}")
    # any attention EXECUTION strategy trains the same weights; decode
    # always attends q against the cache, so attn_impl needs no check.
    # MoE blocks decode too: _mlp re-applies the real MoEMLP module.


def _decoding_for(module, **how):
    """How `DecodeEngine` decodes `module`, decided once when the engine
    is built: the decoding of its architecture (`_DECODINGS`).  `how` is
    what the engine fixes for every call: `cache_dtype` (the layout
    segments carry the state in), `fused` (steps may read through the
    Pallas kernel), `verifies` (the engine speculates) and `hint` (a
    state leaf's sharding hint on the engine's mesh).

    A decoding (`hybrid_lm.Decoding`) has `state_kinds` (WINDOW | FIXED a
    layer), `count_names` (what its programs count on the device; every
    `run_*` returns the counts last), `empty_state`, `resident_params`,
    `run_prompt` + `head` (the head is applied to the gathered row),
    `run_step` / `run_step_rows`, `close_prompt` / `reopen_prompt` (the
    RESIDENT layout: the one a finished prompt's state is carried in
    between calls and a segment steps on as it is; and back, for a prompt
    resumed from donor rows), `relayout_bytes` (what a call of one of
    its programs re-tiles of the state) and `row_writes` (the per-row
    writes a segment makes into window leaves, and those of them that
    loop over the rows).  What only some decodings carry (int8 state,
    the speculative verify segment, the seq-sharded prompt and step,
    partition rules) each says of itself (`cache_dtypes`,
    `speculates`, `shards`): `DecodeEngine.__init__` refuses the rest by
    name."""
    _check_generatable(module, tuple(_DECODINGS), "DecodeEngine")
    return next(decoding for arch, decoding in _DECODINGS.items()
                if isinstance(module, arch))(module, **how)


def resident_variables(module, variables, mesh=None):
    """The weight tree the decode programs of `module` are handed: every
    leaf that they read only through a cast to `module.dtype` is held in
    that dtype, so no program call casts it again (with float32
    parameters and bfloat16 compute XLA hoists those casts out of the
    step loop and repeats them in every prefill and segment call).  The
    model says which leaves those are, beside the code that reads them
    (its decoding's `resident_params`); all others
    keep their dtype.  Rounding once here gives the bits that rounding in
    every call gave, and `.astype` of a leaf already in `dtype` is the
    identity, so every logit is unchanged (bit for bit wherever XLA
    compiles the products alike: always on the CPU; PERF.md section 6
    has the TPU's one exception).  A float32 module gets its tree back
    as it is.

    Pass the tree BEFORE `bridge.place_weights` (the cast commutes with
    replication and sharding).  A leaf is cast where it lives; a host
    leaf bound for the default device (`mesh` None) is placed there first
    and cast there, which is far quicker than numpy's cast, and waited
    for, so that its float32 upload is freed before the next leaf's
    arrives: the two copies of the tree never stand on the device
    together."""
    dtype = jnp.dtype(module.dtype)
    if dtype == jnp.float32:
        return variables
    from mmlspark_tpu.parallel.bridge import place_weights

    def cast(leaf):
        if leaf.dtype == dtype:
            return leaf
        if mesh is None:
            return jax.block_until_ready(place_weights(leaf).astype(dtype))
        return leaf.astype(dtype)

    params = _decoding_for(module).resident_params(variables["params"], cast)
    return {**variables, "params": params}


def filter_logits(logits: jax.Array, top_k: Optional[int] = None,
                  top_p: Optional[float] = None) -> jax.Array:
    """Mask (B, V) logits to the top-k entries and/or the top-p nucleus.

    top_k keeps the k highest-logit tokens per row; top_p keeps the
    smallest prefix of the probability-sorted vocabulary whose cumulative
    probability reaches p (the first token always survives, so the
    distribution never empties).  Everything else becomes NEG_INF —
    static-shape, sort-based, jit-friendly."""
    out = logits.astype(jnp.float32)
    if top_k is not None and top_k < out.shape[-1]:
        kth = jax.lax.top_k(out, top_k)[0][..., -1:]
        out = jnp.where(out >= kth, out, NEG_INF)
    if top_p is not None and top_p < 1.0:
        sorted_logits = jnp.sort(out, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # a token is kept while the mass BEFORE it is < p (so the first
        # token is always kept); find the smallest kept logit
        keep = (cum - probs) < top_p
        cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf),
                         axis=-1, keepdims=True)
        out = jnp.where(out >= cutoff, out, NEG_INF)
    return out


def _validate_decode_args(module, prompt_len: int,
                          max_new_tokens: int) -> None:
    """Shared budget checks for both decode entry points (sampler + beam)."""
    _check_generatable(module)
    if prompt_len < 1:
        raise ValueError("prompt_len must be >= 1")
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    if prompt_len + max_new_tokens > module.max_len:
        raise ValueError(
            f"prompt_len ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds the model's max_len ({module.max_len})")


def _prefill(params, prompts, module, prompt_len: int):
    """Allocate zero caches, run the prompt forward, return (last-position
    logits, caches).  Raises at trace time on a prompt-length mismatch — a
    compiled fn reused at the wrong length would decode against
    never-written cache slots."""
    if prompts.shape[1] != prompt_len:
        raise ValueError(
            f"prompts have length {prompts.shape[1]} but this compiled "
            f"decode program was built for prompt_len={prompt_len}")
    b = prompts.shape[0]
    caches = TransformerDecoding(module, hint=_hint_kv).empty_state(
        b, module.max_len)
    logits, caches = forward_with_cache(params, prompts, caches, 0, module)
    return logits[:, -1], caches


def make_generate_fn(module, prompt_len: int, max_new_tokens: int,
                     temperature: float = 0.0,
                     top_k: Optional[int] = None,
                     top_p: Optional[float] = None):
    """A jitted `(variables, prompts (B, P) int32, rng_key) -> (B, P+N)`
    generation program for one (prompt_len, max_new_tokens) shape class.

    Compiled once per shape class; TextGenerator caches these.  The prompt
    must fit the model: prompt_len + max_new_tokens <= max_len (position
    embeddings are the budget).  Sampling is greedy at temperature 0;
    otherwise temperature-scaled categorical over the top_k / top_p
    (nucleus) filtered distribution (`filter_logits`)."""
    _validate_decode_args(module, prompt_len, max_new_tokens)
    if top_k is not None and top_k < 1:
        raise ValueError("top_k must be >= 1")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError("top_p must be in (0, 1]")
    greedy = temperature <= 0.0

    def sample(logits, key):
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # temperature first, then filter: the nucleus mass is measured on
        # the distribution actually sampled (the standard ordering)
        filtered = filter_logits(
            logits.astype(jnp.float32) / temperature, top_k, top_p)
        return jax.random.categorical(key, filtered,
                                      axis=-1).astype(jnp.int32)

    @jax.jit
    def generate_fn(variables, prompts, key):
        params = variables["params"]
        last_logits, caches = _prefill(params, prompts, module, prompt_len)
        key, sub = jax.random.split(key)
        tok = sample(last_logits, sub)

        def step(carry, step_key):
            tok, pos, caches = carry
            logits, caches = forward_with_cache(
                params, tok[:, None], caches, pos, module)
            nxt = sample(logits[:, 0], step_key)
            return (nxt, pos + 1, caches), tok

        if max_new_tokens > 1:
            (tok, _, _), toks = lax.scan(
                step, (tok, jnp.asarray(prompt_len, jnp.int32), caches),
                jax.random.split(key, max_new_tokens - 1))
            generated = jnp.concatenate(
                [toks.transpose(1, 0), tok[:, None]], axis=1)
        else:
            generated = tok[:, None]
        return jnp.concatenate([prompts, generated], axis=1)

    return generate_fn


def generate(module, variables, prompts, max_new_tokens: int,
             temperature: float = 0.0,
             top_k: Optional[int] = None, top_p: Optional[float] = None,
             rng: Optional[jax.Array] = None) -> np.ndarray:
    """One-shot convenience wrapper around `make_generate_fn` (which is
    the jit-once API for repeated calls)."""
    prompts = jnp.asarray(prompts, jnp.int32)
    fn = make_generate_fn(module, prompts.shape[1], max_new_tokens,
                          temperature, top_k=top_k, top_p=top_p)
    key = rng if rng is not None else jax.random.key(0)
    return np.asarray(fn(variables, prompts, key))


def make_beam_search_fn(module, prompt_len: int, max_new_tokens: int,
                        beam_width: int):
    """A jitted `(variables, prompts (B, P) int32) -> (tokens, scores)`
    beam-search program: tokens (B, W, P+N) ordered best-first per row,
    scores (B, W) the summed token log-probabilities of each beam's
    generated region.

    Deterministic length-N beams (token-id models here carry no reserved
    EOS, so no early stopping and no length penalty — all candidates have
    equal length and rank directly by total log-probability).  Mechanics:
    the prompt prefills ONCE per row, caches are then expanded to B*W
    rows, and each scan step scores all beams' vocab expansions, keeps
    the top W of W*V per row, and RE-INDEXES both the cache rows and the
    token history to the surviving beams' ancestors — static shapes
    throughout, so the whole search is one compiled program."""
    _validate_decode_args(module, prompt_len, max_new_tokens)
    if beam_width < 1:
        raise ValueError("beam_width must be >= 1")
    if beam_width > module.vocab_size:
        raise ValueError(
            f"beam_width ({beam_width}) cannot exceed the vocabulary "
            f"({module.vocab_size}): the first expansion keeps beam_width "
            "distinct tokens")
    w = beam_width

    @jax.jit
    def beam_fn(variables, prompts):
        params = variables["params"]
        b = prompts.shape[0]
        v = module.vocab_size
        last_logits, caches = _prefill(params, prompts, module, prompt_len)
        logprobs = jax.nn.log_softmax(last_logits, axis=-1)     # (B, V)
        scores, tok = lax.top_k(logprobs, w)                    # (B, W)
        tok = tok.astype(jnp.int32)
        # every beam of a row shares the prompt's cache: expand B -> B*W
        caches = [(jnp.repeat(kc, w, axis=0), jnp.repeat(vc, w, axis=0))
                  for kc, vc in caches]
        history = jnp.zeros((b, w, max_new_tokens), jnp.int32)
        history = history.at[:, :, 0].set(tok)
        row_base = jnp.arange(b)[:, None] * w                   # (B, 1)

        def step(carry, t):
            tok, scores, history, caches = carry
            logits, caches = forward_with_cache(
                params, tok.reshape(b * w, 1), caches,
                prompt_len + t, module)
            logprobs = jax.nn.log_softmax(
                logits[:, 0], axis=-1).reshape(b, w, v)
            total = scores[:, :, None] + logprobs               # (B, W, V)
            scores, flat_idx = lax.top_k(total.reshape(b, w * v), w)
            beam_idx = flat_idx // v                            # ancestor
            tok = (flat_idx % v).astype(jnp.int32)
            take = (row_base + beam_idx).reshape(-1)            # (B*W,)
            caches = [(kc[take], vc[take]) for kc, vc in caches]
            history = jnp.take_along_axis(
                history, beam_idx[:, :, None], axis=1)
            history = history.at[:, :, t + 1].set(tok)
            return (tok, scores, history, caches), None

        if max_new_tokens > 1:
            (tok, scores, history, caches), _ = lax.scan(
                step, (tok, scores, history, caches),
                jnp.arange(max_new_tokens - 1))
        tokens = jnp.concatenate(
            [jnp.broadcast_to(prompts[:, None], (b, w, prompt_len)),
             history], axis=2)
        return tokens, scores

    return beam_fn


def beam_search(module, variables, prompts, max_new_tokens: int,
                beam_width: int = 4):
    """One-shot convenience wrapper around `make_beam_search_fn`.
    Returns (tokens (B, W, P+N) best-first, scores (B, W))."""
    prompts = jnp.asarray(prompts, jnp.int32)
    fn = make_beam_search_fn(module, prompts.shape[1], max_new_tokens,
                             beam_width)
    tokens, scores = fn(variables, prompts)
    return np.asarray(tokens), np.asarray(scores)


# ---------------------------------------------------------------------------
# The decode engine: bucketed prefill + cache-windowed segments + early exit
# ---------------------------------------------------------------------------

DEFAULT_CACHE_CHUNK = 128  # cache-window growth granularity (slots): the
# compiled decode step attends over the cache prefix rounded up to this,
# so steady-step bandwidth tracks occupancy in chunk-sized increments
DEFAULT_MIN_BUCKET = 8     # smallest prompt bucket: below this, shape-class
# consolidation saves more than the pad compute costs


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def bucket_length(n: int, max_len: int, max_new_tokens: int,
                  min_bucket: int = DEFAULT_MIN_BUCKET) -> int:
    """The prompt bucket for a true length `n`: next power of two, floored
    at `min_bucket` and capped at `max_len - max_new_tokens` (the cap keeps
    every bucket decodable to the full generation budget; position
    embeddings are indexed by TRUE per-row positions, so the cap — not the
    bucket's pad tail — is what the position table bounds)."""
    cap = max_len - max_new_tokens
    if n < 1:
        raise ValueError("prompt length must be >= 1")
    if n > cap:
        raise ValueError(
            f"prompt length ({n}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds the model's max_len ({max_len})")
    return min(max(1 << (n - 1).bit_length(), min_bucket), cap)


def decode_segments(bucket: int, max_new_tokens: int,
                    chunk: int) -> list:
    """The static segment plan for a windowed decode: a list of
    (start_step, seg_len, window) covering scan steps 0..max_new_tokens-2
    (step s writes cache slot bucket+s; the first generated token comes
    from prefill).  `window` is the chunk-rounded cover of the segment's
    highest written slot, and segments are additionally capped at `chunk`
    steps so the early-exit host check runs at least once per chunk."""
    if chunk < 1:
        raise ValueError("chunk must be >= 1")
    segs = []
    s = 0
    while s <= max_new_tokens - 2:
        w = _round_up(bucket + s + 1, chunk)
        last = min(w - bucket - 1, s + chunk - 1, max_new_tokens - 2)
        segs.append((s, last - s + 1, w))
        s = last + 1
    return segs


def _make_sampler(temperature: float, top_k, top_p):
    """A `(logits (B, V), row_keys (B,), step) -> tokens (B,)` sampler with
    per-row keys: each row's stream is `fold_in(row_key, step)`, so a
    row's draws depend only on (its key, the step index) — never on which
    rows share its batch or how groups were formed.  `step` is one scalar
    for rows in step, or a (B,) vector: rows at different decode offsets
    (the serving engine's continuous batch) draw from exactly the stream
    positions the uniform-step batch would have given them."""
    if temperature <= 0.0:
        return lambda logits, row_keys, step: jnp.argmax(
            logits, axis=-1).astype(jnp.int32)

    def sample(logits, row_keys, step):
        filtered = filter_logits(
            logits.astype(jnp.float32) / temperature, top_k, top_p)
        keys = jax.vmap(jax.random.fold_in, in_axes=(
            0, 0 if jnp.ndim(step) else None))(row_keys, step)
        return jax.vmap(jax.random.categorical)(
            keys, filtered).astype(jnp.int32)
    return sample


def _make_stop_check(stop_tokens: tuple):
    if not stop_tokens:
        return lambda tok: jnp.zeros(tok.shape, bool)
    stops = jnp.asarray(list(stop_tokens), jnp.int32)
    return lambda tok: (tok[:, None] == stops[None, :]).any(axis=-1)


def _grow_cache(cache: jax.Array, window: int) -> jax.Array:
    """Zero-extend a cache prefix to `window` slots (static shapes).
    Rank-agnostic over the trailing axes: the (B, W, H, D) payloads and
    the (B, W, H) int8-cache scale arrays grow the same way."""
    w_in = cache.shape[1]
    if w_in == window:
        return cache
    pad = [(0, 0), (0, window - w_in)] + [(0, 0)] * (cache.ndim - 2)
    return jnp.pad(cache, pad)


def _grow_state(caches: list, window: int, kinds=None,
                hint=_hint_kv) -> list:
    """`_grow_cache` over every layer's leaves.  `kinds` names each
    layer's state kind (None: every layer a window, TransformerLM's): a
    FIXED layer's leaves are row-indexed only and pass through.  A window
    leaf narrower than its layer's first (an indexer's compressed keys, one
    a stride of slots) keeps its share of the window."""
    return [layer if kinds is not None and kinds[i] == FIXED
            else tuple(hint(_grow_cache(
                c, window * c.shape[1] // layer[0].shape[1])) for c in layer)
            for i, layer in enumerate(caches)]


def _state_window(caches: list, kinds=None) -> int:
    """Slots the state's window layers hold (0: it has none)."""
    for i, layer in enumerate(caches):
        if kinds is None or kinds[i] == WINDOW:
            return int(layer[0].shape[1])
    return 0


@functools.partial(jax.jit, static_argnames=("kinds",))
def _merge_cache_rows_jit(dst_caches, src_caches, di, si, kinds=None):
    window = max(_state_window(dst_caches, kinds),
                 _state_window(src_caches, kinds))
    dst_caches = _grow_state(dst_caches, window, kinds, lambda c: c)
    src_caches = _grow_state(src_caches, window, kinds, lambda c: c)
    merged = []
    for i, (dst_layer, src_layer) in enumerate(zip(dst_caches, src_caches)):
        hint = (_hint_kv if kinds is None or kinds[i] == WINDOW
                else lambda c: c)
        merged.append(tuple(hint(d.at[di].set(s[si]))
                            for d, s in zip(dst_layer, src_layer)))
    return merged


_PAGE_LAYOUT = None  # lazy structs for the KV-page wire layout


def _page_structs():
    global _PAGE_LAYOUT
    if _PAGE_LAYOUT is None:
        import struct
        # page header (n_layers, n_tensors); per-tensor header
        # (dtype-name length, ndim); dims and byte lengths as >I
        _PAGE_LAYOUT = (struct.Struct(">HH"), struct.Struct(">BB"),
                        struct.Struct(">I"))
    return _PAGE_LAYOUT


def _wire_dtype(name: str) -> np.dtype:
    """Resolve a serialized dtype name, including the ml_dtypes extras
    (bfloat16 is the default model dtype and has no native numpy name —
    np.save would silently degrade it to a void dtype)."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        try:
            return np.dtype(getattr(ml_dtypes, name))
        except (AttributeError, TypeError) as e:
            raise ValueError(f"unknown page tensor dtype {name!r}") from e


def serialize_cache_row(caches, row: int, chunk: int) -> list:
    """Cut ONE row of a serve cache into chunk-granular window pages for
    the prefill->decode handoff: each page is a self-describing blob
    (dtype name + shape + raw bytes per layer-tensor window slice) that
    `deserialize_cache_row` reassembles without any side-channel layout
    info.  Works for both cache layouts — the 2-tuple model-dtype (k, v)
    and the 4-tuple int8 (kq, k_scale, vq, v_scale); int8 pages
    naturally shrink the wire bytes, which is the point of quantizing
    BEFORE shipping.  The explicit dtype name (not npy) is what keeps
    bfloat16 byte-exact across the wire.  Seq-sharded caches gather here
    IMPLICITLY: `np.asarray` on a sharded row pulls the full window to
    host — fine on single-process (fully-addressable) meshes, which is
    the only place this serializer runs."""
    import io
    page_hdr, tens_hdr, u32 = _page_structs()
    host = [[np.asarray(t[row]) for t in layer] for layer in caches]
    width = host[0][0].shape[0]
    pages = []
    for lo in range(0, width, max(1, int(chunk))):
        hi = min(width, lo + max(1, int(chunk)))
        bio = io.BytesIO()
        bio.write(page_hdr.pack(len(host), len(host[0])))
        for layer in host:
            for tensor in layer:
                part = np.ascontiguousarray(tensor[lo:hi])
                name = part.dtype.name.encode("ascii")
                bio.write(tens_hdr.pack(len(name), part.ndim))
                bio.write(name)
                for dim in part.shape:
                    bio.write(u32.pack(dim))
                raw = part.tobytes()
                bio.write(u32.pack(len(raw)))
                bio.write(raw)
        pages.append(bio.getvalue())
    return pages


def deserialize_cache_row(pages: list) -> list:
    """Reassemble `serialize_cache_row` pages (in chunk order) into a
    1-row cache ready for `DecodeEngine.merge_cache_rows` — window
    slices concatenate back on the window axis and gain the batch dim.
    Byte-exact: dtype and bits round-trip untouched."""
    import io
    if not pages:
        raise ValueError("cannot deserialize an empty page list")
    page_hdr, tens_hdr, u32 = _page_structs()

    def read(bio, n):
        data = bio.read(n)
        if len(data) != n:
            raise ValueError("short page: truncated tensor record")
        return data

    parts = None
    for blob in pages:
        bio = io.BytesIO(blob)
        n_layers, n_tensors = page_hdr.unpack(read(bio, page_hdr.size))
        if parts is None:
            parts = [[[] for _ in range(n_tensors)]
                     for _ in range(n_layers)]
        elif len(parts) != n_layers or len(parts[0]) != n_tensors:
            raise ValueError("page layout mismatch across pages")
        for li in range(n_layers):
            for ti in range(n_tensors):
                nlen, ndim = tens_hdr.unpack(read(bio, tens_hdr.size))
                dtype = _wire_dtype(read(bio, nlen).decode("ascii"))
                shape = tuple(u32.unpack(read(bio, u32.size))[0]
                              for _ in range(ndim))
                (nbytes,) = u32.unpack(read(bio, u32.size))
                arr = np.frombuffer(read(bio, nbytes), dtype=dtype)
                parts[li][ti].append(arr.reshape(shape))
    return [tuple(jnp.asarray(np.concatenate(tensors, axis=0))[None]
                  for tensors in layer)
            for layer in parts]


class DecodeEngine:
    """Bucketed, cache-windowed, early-exit generation for one sampling
    configuration (the module docstring has the design).

    Two jitted programs serve every bucket: `_prefill` (specialized per
    (batch, bucket) shape) and `_segment` (specialized per (batch,
    window-in, window, seg_len) — bucket and step offsets are traced
    scalars, so buckets whose windows coincide share compiled segments).
    `compiled_programs` counts the distinct shape classes built so far —
    the number the ragged-workload bench pins.

    `cache_dtype='int8'` stores the KV cache quantized (per-head symmetric
    int8, quantize-on-write; dequant inside the attention read,
    ops/attention.py) — the steady decode step streams 1 byte per cached
    element instead of the model dtype's 2-4, which is the win on a
    bandwidth-bound step.  Quantizing the cache changes numerics (~1/254
    relative per element), so near-tie greedy choices can flip; top-1
    agreement with the model-dtype cache is test-pinned on a fixed-seed
    model, and bench reports the agreement next to the step-time speedup.

    Greedy token parity with `make_generate_fn`'s full-cache per-length
    decoder is exact at float32 (test-pinned): pad slots carry exactly
    zero attention weight and positions are per-row true positions, so
    bucketing and windowing are pure layout.  For bfloat16 bundles the
    same caveat as the module docstring's recompute-parity note applies:
    padded-shape matmuls can tile differently at bf16 resolution, so
    near-tie greedy choices (top-2 gap of one bf16 ulp) may legitimately
    resolve differently between bucket layouts.
    """

    def __init__(self, module, max_new_tokens: int, *,
                 temperature: float = 0.0,
                 top_k: Optional[int] = None, top_p: Optional[float] = None,
                 stop_tokens: tuple = (),
                 chunk: int = DEFAULT_CACHE_CHUNK,
                 min_bucket: int = DEFAULT_MIN_BUCKET,
                 cache_dtype: str = "model", mesh=None,
                 min_new_tokens: int = 1,
                 prefill_chunk: Optional[int] = None,
                 draft_module=None, spec_tokens: int = 0):
        seq_shards = (int(mesh.shape.get(SEQ_AXIS, 1))
                      if mesh is not None else 1)
        # the fused Pallas single-query kernel only runs single-device:
        # pallas_call has no SPMD partitioning rule, so under a mesh the
        # decode step keeps the einsum composition GSPMD can shard.  (The
        # kernel itself degrades to the same reference off-TPU — tier-1
        # CPU runs exercise that fallback on this very path.)
        fused = mesh is None
        # a window leaf's hint on this mesh: slots over 'seq' where the
        # mesh has it (1 = the classic whole-window engine), else heads
        # on 'model'
        hint = _hint_seq_kv if seq_shards > 1 else _hint_kv
        decoding = _decoding_for(
            module, cache_dtype=cache_dtype, fused=fused, hint=hint,
            verifies=bool(spec_tokens or draft_module is not None))
        # what the model's decoding does not carry refuses here, by name;
        # nothing falls back
        name = type(module).__name__
        if cache_dtype == "int8" and "int8" not in decoding.cache_dtypes:
            raise ValueError(
                f"cache_dtype='int8' is not supported for {name}: its "
                "layers' state has no quantized layout")
        if ((draft_module is not None or spec_tokens)
                and not decoding.speculates):
            raise ValueError(
                f"speculative decoding is not supported for {name}: the "
                "multi-token verify forward has no path through its "
                "layers' state")
        if mesh is not None and not decoding.shards and (
                seq_shards > 1 or int(mesh.shape.get(MODEL_AXIS, 1)) > 1):
            raise ValueError(
                f"a mesh with model>1 or seq>1 is not supported for "
                f"{name}: its weights and state have no partition rules "
                "(a data-only mesh works)")
        if cache_dtype not in ("model", "int8"):
            raise ValueError(
                f"unknown cache_dtype '{cache_dtype}' (model | int8)")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if max_new_tokens >= module.max_len:
            raise ValueError(
                f"max_new_tokens ({max_new_tokens}) leaves no room for a "
                f"prompt within max_len ({module.max_len})")
        if top_k is not None and top_k < 1:
            raise ValueError("top_k must be >= 1")
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError("top_p must be in (0, 1]")
        if chunk < 1:
            raise ValueError("chunk must be >= 1")
        if not 1 <= min_new_tokens <= max_new_tokens:
            raise ValueError(
                f"min_new_tokens ({min_new_tokens}) must be in "
                f"1..max_new_tokens ({max_new_tokens})")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(
                "prefill_chunk must be >= 1 (None = whole-prompt)")
        if spec_tokens < 0:
            raise ValueError("spec_tokens must be >= 0")
        if spec_tokens and draft_module is None:
            raise ValueError(
                "spec_tokens > 0 needs a draft_module (zoo/speculative.py "
                "builds one from a target bundle)")
        if draft_module is not None:
            if spec_tokens < 1:
                raise ValueError("draft_module set but spec_tokens is 0")
            _check_generatable(draft_module)
            if draft_module.vocab_size != module.vocab_size:
                raise ValueError(
                    f"draft vocab ({draft_module.vocab_size}) != target "
                    f"vocab ({module.vocab_size}): speculative acceptance "
                    "compares distributions over one vocabulary")
            if draft_module.max_len < module.max_len:
                raise ValueError(
                    f"draft max_len ({draft_module.max_len}) < target "
                    f"max_len ({module.max_len}): the draft must reach "
                    "every position the target decodes")
            if module.mlp_impl == "moe" or draft_module.mlp_impl == "moe":
                raise ValueError(
                    "speculative decoding does not support MoE models: "
                    "the multi-token verify forward routes a different "
                    "capacity group than step-by-step decode, so "
                    "greedy-exactness cannot hold (see _mlp)")
        stop_tokens = tuple(int(t) for t in stop_tokens or ())
        for t in stop_tokens:
            if not 0 <= t < module.vocab_size:
                raise ValueError(
                    f"stop token {t} outside the vocabulary "
                    f"(0..{module.vocab_size - 1})")
        if seq_shards > 1:
            # the seq-sharded engine path: long-context decode with the
            # KV window partitioned over 'seq'.  Its refusals bound the
            # composition space — everything below is a real algorithmic
            # conflict, not a not-yet
            if int(mesh.shape.get(MODEL_AXIS, 1)) > 1:
                raise ValueError(
                    "seq-sharded decode (mesh seq>1) does not compose "
                    "with model>1: the seq path keeps heads unsharded "
                    "(SEQ_KV_CACHE_SPEC) so the stats merge is the only "
                    "cross-chip attention collective")
            if module.mlp_impl == "moe":
                raise ValueError(
                    "seq-sharded decode does not support MoE models: "
                    "per-shard expert routing would diverge from the "
                    "global capacity groups (see _mlp)")
            if draft_module is not None:
                raise ValueError(
                    "seq-sharded decode does not compose with "
                    "speculative decoding: the multi-token verify "
                    "forward has no seq-sharded cache path")
            if prefill_chunk is not None:
                raise ValueError(
                    "seq-sharded decode does not compose with chunked "
                    "prefill: distributed blockwise (ring) prefill "
                    "already splits the prompt over chips")
            if chunk % seq_shards:
                raise ValueError(
                    f"cache chunk ({chunk}) must divide by the mesh seq "
                    f"axis ({seq_shards}) so every window width shards "
                    "evenly")
            if min_bucket % seq_shards:
                raise ValueError(
                    f"min_bucket ({min_bucket}) must divide by the mesh "
                    f"seq axis ({seq_shards}) so every prompt bucket "
                    "shards evenly")
        self.module = module
        # each layer's state kind (a K/V window that grows by `chunk`, or
        # a fixed per-row leaf) and the names of what the programs count
        # on the device: asked of the model once, here
        self._decoding = decoding
        self._draft_decoding = draft = (
            _decoding_for(draft_module, hint=_hint_draft_kv)
            if draft_module is not None else None)
        self.state_kinds = kinds = decoding.state_kinds
        self.count_names = decoding.count_names
        self.counts_out: list = []   # the last program's device counts
        # bytes of state that the serving hooks' programs re-tiled, summed
        # at each dispatch from what the decoding says of the program
        # (`Decoding.relayout_bytes`: shapes alone, nothing is fetched)
        self.relayout_bytes = 0
        # per-row writes into window leaves that the dispatched segments
        # and speculative rounds make, and those of them in the looped
        # form (`Decoding.row_writes`: shapes alone)
        self.row_writes = self.row_writes_looped = 0
        self._chunk_counts: list = []
        self.max_new_tokens = max_new_tokens
        self.stop_tokens = stop_tokens
        self.chunk = chunk
        self.min_bucket = min_bucket
        self.cache_dtype = cache_dtype
        self.min_new_tokens = min_new_tokens
        self.prefill_chunk = prefill_chunk
        self.draft_module = draft_module
        self.spec_tokens = spec_tokens
        # the mesh the KV hints target: every compiled program (prefill,
        # segments, merge) traces under use_mesh(mesh), so at mp >= 2 the
        # cache keeps heads on 'model' end to end; None = single-device
        self.mesh = mesh
        self.seq_shards = seq_shards
        greedy = temperature <= 0.0
        sample = _make_sampler(temperature,
                               None if greedy else top_k,
                               None if greedy else top_p)
        is_stop = _make_stop_check(stop_tokens)
        min_new = min_new_tokens

        def stop_gate(tok, new_count):
            # a stop token only freezes once the row has emitted
            # `min_new_tokens` tokens INCLUDING it; `new_count` is that
            # count (a python int at prefill, traced in segment scans)
            if min_new <= 1:
                return is_stop(tok)
            return is_stop(tok) & (new_count >= min_new)

        # Every `run_*` of the decoding returns `counts` last, a tuple of
        # device counters that rides with the tokens: () where the model
        # counts nothing.
        no_counts = ((jnp.zeros(len(self.count_names), jnp.float32),)
                     if self.count_names else ())

        def add_counts(counts, new):
            return tuple(c + n for c, n in zip(counts, new))

        def grow(caches, window):
            return _grow_state(caches, window, kinds, hint)

        def row_logits(params, feats, idx):
            """Each row's logits at position `idx` (B,) of what
            `run_prompt` returned for a segment: the head sees the
            gathered row only."""
            return decoding.head(params, jnp.take_along_axis(
                feats, idx[:, None, None], axis=1)[:, 0])

        def prefill_impl(variables, prompts, true_len, live, row_keys):
            params = variables["params"]
            w0 = _round_up(prompts.shape[1] + 1, chunk)
            # called here, not through a helper: the flash kernels trace
            # a frame nearer the stack's base so (PERF.md section 7)
            if seq_shards > 1:
                feats, caches, counts = seq_prompt(params, prompts, w0)
            else:
                feats, caches, counts = decoding.run_prompt(
                    params, prompts,
                    decoding.empty_state(prompts.shape[0], w0), 0, true_len,
                    live)
            return prefill_finish_impl(
                caches, row_logits(params, feats, true_len - 1), live,
                row_keys) + counts

        def uniform_steps(run_step, seg_len, slots, params, caches, tok,
                          done, true_len, bucket, t0, row_keys):
            """`seg_len` decode steps of rows that share their step offset
            (and so their write slot) over the window `slots` names."""
            def step(carry, s_off):
                tok, done, caches, counts = carry
                t = t0 + s_off
                slot = bucket + t
                pos = true_len + t
                visible = ((slots[None, :] < true_len[:, None])
                           | ((slots[None, :] >= bucket)
                              & (slots[None, :] <= slot)))
                logits, caches, new = run_step(params, tok, pos, slot,
                                               caches, visible, ~done)
                nxt = sample(logits, row_keys, t + 1)
                nxt = jnp.where(done, tok, nxt)
                return (nxt, done | stop_gate(nxt, t + 2), caches,
                        add_counts(counts, new)), tok

            (tok, done, caches, counts), toks = lax.scan(
                step, (tok, done, caches, no_counts), jnp.arange(seg_len))
            return (caches, toks.transpose(1, 0), tok, done) + counts

        def segment_impl(seg_len, window, variables, caches, tok, done,
                         true_len, bucket, t0, row_keys):
            return uniform_steps(
                decoding.run_step, seg_len, jnp.arange(window),
                variables["params"], grow(caches, window), tok, done,
                true_len, bucket, t0, row_keys)

        if seq_shards > 1:
            # SEQ-SHARDED engine: the prompt forward and the segment run
            # in shard_map regions over 'seq'.  Prefill runs DISTRIBUTED
            # BLOCKWISE (ring attention over the prompt slabs — wall clock
            # ~1/n); decode keeps the host segment loop identical but
            # merges per-chip softmax stats across 'seq' each step.
            from jax.sharding import PartitionSpec as P
            from mmlspark_tpu.parallel.ring import _shard_map
            row_spec = P(DATA_AXIS)

            def seq_prompt(params, prompts, w0):
                logits, kvs = _shard_map(
                    decoding.run_prompt_seq, mesh=mesh,
                    in_specs=(P(), P(DATA_AXIS, SEQ_AXIS)),
                    out_specs=(P(DATA_AXIS, SEQ_AXIS, None),
                               SEQ_KV_CACHE_SPEC))(params, prompts)
                # the cache window (w0, chunk-aligned) has DIFFERENT seq
                # partition boundaries than the prompt (p): pad outside
                # the shard_map and let GSPMD reshard once against the
                # hint — not inside, where slab widths would disagree
                return logits, grow(kvs, w0), ()

            def segment_impl(seg_len, window, variables, caches, tok,
                             done, true_len, bucket, t0, row_keys):
                caches = grow(caches, window)
                cache_specs = [tuple(SEQ_KV_CACHE_SPEC if c.ndim == 4
                                     else SEQ_KV_SCALE_SPEC for c in layer)
                               for layer in caches]

                def local_seg(params, caches, tok, done, true_len, bucket,
                              t0, rk):
                    w_l = caches[0][0].shape[1]
                    lo = lax.axis_index(SEQ_AXIS) * w_l
                    return uniform_steps(
                        functools.partial(decoding.run_step_seq, lo=lo),
                        seg_len, lo + jnp.arange(w_l), params, caches, tok,
                        done, true_len, bucket, t0,
                        jax.random.wrap_key_data(rk))

                # typed PRNG keys are an extended dtype shard_map can't
                # always carry (jax 0.4.x): thread the raw uint32 key
                # data through and rebuild inside
                return _shard_map(
                    local_seg, mesh=mesh,
                    in_specs=(P(), cache_specs, row_spec, row_spec,
                              row_spec, P(), P(), P(DATA_AXIS, None)),
                    out_specs=(cache_specs, P(DATA_AXIS, None), row_spec,
                               row_spec))(
                    variables["params"], caches, tok, done, true_len,
                    bucket, t0, jax.random.key_data(row_keys))

        def serve_segment_impl(seg_len, window, variables, caches, tok,
                               done, true_len, budget, bucket, t_row,
                               row_keys):
            """The continuous-batching decode segment (serve/engine.py):
            rows carry PER-ROW step offsets `t_row` (joined rows start at
            0 while resident rows are mid-generation) and per-row token
            budgets, so one compiled program advances a mixed-age batch
            `seg_len` steps.  Rows freeze on stop/budget/done exactly as
            the uniform-step segment; frozen rows' writes land in their
            own cache row only and their emissions repeat the frozen
            token (the engine's per-row emit counters ignore them)."""
            params = variables["params"]
            caches = grow(caches, window)
            slots_axis = jnp.arange(window)
            max_pos = module.max_len - 1

            def step(carry, s_off):
                tok, done, caches, counts = carry
                t = t_row + s_off                     # (B,) per-row step
                slot = jnp.minimum(bucket + t, window - 1)
                pos = jnp.minimum(true_len + t, max_pos)
                visible = ((slots_axis[None, :] < true_len[:, None])
                           | ((slots_axis[None, :] >= bucket)
                              & (slots_axis[None, :] <= slot[:, None])))
                logits, caches, new = decoding.run_step_rows(
                    params, tok, pos, slot, caches, visible, ~done)
                nxt = sample(logits, row_keys, t + 1)
                nxt = jnp.where(done, tok, nxt)
                done = done | stop_gate(nxt, t + 2) | (t + 1 >= budget)
                return (nxt, done, caches, add_counts(counts, new)), nxt

            (tok, done, caches, counts), toks = lax.scan(
                step, (tok, done, caches, no_counts), jnp.arange(seg_len))
            return (caches, toks.transpose(1, 0), tok, done) + counts

        def prefill_chunk0_impl(w0, variables, tokens, true_len):
            """First chunk of a CHUNKED prefill (offset 0): allocates the
            window-`w0` caches and seeds the running last-prompt-position
            logits.  Chunking splits the prompt forward so the serving
            engine can interleave it with resident decode segments — a
            long prompt stops stalling running requests."""
            params = variables["params"]
            b, cl = tokens.shape
            caches = decoding.empty_state(b, w0)
            feats, caches, counts = decoding.run_prompt(
                params, tokens, caches, 0, true_len, jnp.ones(b, bool))
            last = row_logits(params, feats,
                              jnp.clip(true_len - 1, 0, cl - 1))
            return (caches, last) + counts

        def prefill_chunk_impl(variables, tokens, caches, last, true_len,
                               c0):
            """One later prompt chunk at TRACED offset `c0`: a prompt
            segment's read of the whole window works at any position, so
            every chunk index shares ONE compiled program per shape class.
            Rows whose last prompt token falls inside this chunk update
            the running last-position logits."""
            params = variables["params"]
            b, cl = tokens.shape
            feats, caches, counts = decoding.run_prompt(
                params, tokens, caches, c0, true_len, jnp.ones(b, bool))
            cand = row_logits(params, feats,
                              jnp.clip(true_len - 1 - c0, 0, cl - 1))
            here = (true_len - 1 >= c0) & (true_len - 1 < c0 + cl)
            last = jnp.where(here[:, None], cand, last)
            return (caches, last) + counts

        def prefill_finish_impl(caches, last, live, row_keys):
            """Close a prefill, whole or chunked: sample the first token
            and hand the prompt's state over as segments carry it (an
            int8 cache quantizes here), as (tok, done, caches)."""
            tok = sample(last, row_keys, 0)
            done = ~live | stop_gate(tok, 1)
            return tok, done, decoding.close_prompt(caches)

        def resume_init_impl(w0, row_caches):
            """Open a RESUMED chunked prefill from a donor prefix row
            (serve/prefix_cache.py): reopen the donor slots (suffix chunks
            keep writing through the same state the fresh path uses;
            `prefill_finish_impl` closes the whole window again), grow
            to the bucket window, and zero the running last-position
            logits — the matched prefix is always strictly inside the
            prompt, so a later chunk's `here` mask recomputes them."""
            caches = grow(decoding.reopen_prompt(row_caches), w0)
            b = row_caches[0][0].shape[0]
            last = jnp.zeros((b, module.vocab_size), jnp.float32)
            return caches, last

        def draft_prefill_impl(draft_variables, prompts):
            """Prefill the DRAFT model's cache over the prompt
            (speculative decoding) — same window arithmetic as the
            target prefill, no sampling: the draft's first proposal
            comes from its first round step.  Draft caches stay
            model-dtype (the draft is latency-sized; int8's bandwidth
            win is a target-cache story) and replicate their heads under
            a mesh (DRAFT_KV_CACHE_SPEC)."""
            b, p = prompts.shape
            caches = draft.empty_state(b, _round_up(p + 1, chunk))
            # the draft is a TransformerLM: its prompt needs no lengths
            return draft.run_prompt(draft_variables["params"], prompts,
                                    caches, 0, None, None)[1]

        k_spec = spec_tokens

        def spec_round_impl(window, variables, draft_variables, caches,
                            draft_caches, tok, done, true_len, budget,
                            bucket, t_row, round_idx, row_keys):
            """One speculative round over a mixed-age batch (generate()
            and the serving engine share this program): the draft model
            proposes `spec_tokens` tokens with k+1 cheap single-token
            steps (the extra step back-fills the last proposal's
            draft-cache slot, so the draft never attends a zero slot),
            ONE target forward scores every proposal
            (the decoding's `run_verify`), and the agreeing prefix commits.

            Greedy mode accepts while the proposal equals the target
            argmax and appends the target's own next token — the
            committed stream IS the target's greedy chain by
            construction.  Sampler mode runs standard rejection
            sampling: accept d ~ q(draft) with probability min(1,
            p(d)/q(d)); on rejection draw from the residual
            max(p - q, 0)/Z — each committed token is distributed
            exactly as a target-model draw, whatever the draft proposes.

            Rejected proposals leave garbage K/V past a row's committed
            frontier; visibility is strictly causal in committed slots,
            so those bytes are never read, and the next round overwrites
            them in order.  Returns (caches, draft_caches,
            toks (B, k+1), counts (B,), tok, done, accepted (B,)):
            `counts[r]` leading entries of row r's `toks` are real
            committed tokens (the rest repeat the frozen token);
            `accepted` is the raw draft/target agreement length for
            acceptance-rate telemetry."""
            params = variables["params"]
            dparams = draft_variables["params"]
            caches = grow(caches, window)
            draft_caches = _grow_state(draft_caches, window,
                                       hint=_hint_draft_kv)
            b = tok.shape[0]
            s = k_spec + 1
            slots_axis = jnp.arange(window)
            max_pos = module.max_len - 1
            sampling = not greedy

            # -- draft: k+1 single-token steps (proposals from the first
            # k; the last only writes K/V so the draft cache covers
            # every slot its next round will attend) --
            d_toks = []
            d_dists = []
            cur = tok
            for j in range(s):
                t = t_row + j
                slot = jnp.minimum(bucket + t, window - 1)
                pos = jnp.minimum(true_len + t, max_pos)
                visible = ((slots_axis[None, :] < true_len[:, None])
                           | ((slots_axis[None, :] >= bucket)
                              & (slots_axis[None, :] <= slot[:, None])))
                dlogits, draft_caches, _ = draft.run_step_rows(
                    dparams, cur, pos, slot, draft_caches, visible, ~done)
                if j == k_spec:
                    break          # K/V back-fill only; proposal unused
                if sampling:
                    fd = filter_logits(dlogits / temperature, top_k,
                                       top_p)
                    keys = jax.vmap(
                        lambda rk, jj=j: jax.random.fold_in(
                            jax.random.fold_in(
                                rk, _SPEC_DRAFT_STREAM + round_idx),
                            jj))(row_keys)
                    nxt = jax.vmap(jax.random.categorical)(
                        keys, fd).astype(jnp.int32)
                    d_dists.append(jax.nn.softmax(fd, axis=-1))
                else:
                    nxt = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
                d_toks.append(nxt)
                cur = nxt
            d = jnp.stack(d_toks, axis=1)                       # (B, k)

            # -- verify: one target forward over [tok, d_1..d_k]; token
            # index t_row+j's K/V lands at slot bucket+t_row+j, the same
            # invariant the per-step path keeps --
            xs = jnp.concatenate([tok[:, None], d], axis=1)     # (B, S)
            slots0 = jnp.minimum(bucket + t_row, window - s)
            q_idx = jnp.arange(s)
            vis = ((slots_axis[None, None, :] < true_len[:, None, None])
                   | ((slots_axis[None, None, :] >= bucket)
                      & (slots_axis[None, None, :]
                         <= (slots0[:, None]
                             + q_idx[None, :])[:, :, None])))
            logits, caches = decoding.run_verify(
                params, xs, true_len + t_row, slots0, caches, vis)

            # -- accept --
            if sampling:
                ft = filter_logits(logits / temperature, top_k, top_p)
                pt = jax.nn.softmax(ft, axis=-1)                # (B,S,V)
                qd = jnp.stack(d_dists, axis=1)                 # (B,k,V)
                pt_d = jnp.take_along_axis(
                    pt[:, :k_spec], d[..., None], axis=2)[..., 0]
                qd_d = jnp.take_along_axis(
                    qd, d[..., None], axis=2)[..., 0]
                coin_keys = jax.vmap(lambda rk: jax.random.fold_in(
                    rk, _SPEC_COIN_STREAM + round_idx))(row_keys)
                u = jax.vmap(lambda kk: jax.random.uniform(
                    kk, (k_spec,)))(coin_keys)
                accept = u * jnp.maximum(qd_d, 1e-30) < pt_d    # (B, k)
                n_acc = jnp.cumprod(accept.astype(jnp.int32),
                                    axis=1).sum(axis=1)
                # residual at every position; position k's draft dist is
                # empty, so its residual is the target dist itself — the
                # all-accepted bonus draw falls out of the same formula
                qd_ext = jnp.concatenate(
                    [qd, jnp.zeros((b, 1, qd.shape[-1]), qd.dtype)],
                    axis=1)
                res = jnp.maximum(pt - qd_ext, 0.0)
                mass = res.sum(axis=-1, keepdims=True)
                res = jnp.where(mass > 1e-30, res, pt)  # p == q guard
                fkeys = jax.vmap(lambda rk: jax.vmap(
                    lambda jj: jax.random.fold_in(
                        jax.random.fold_in(
                            rk, _SPEC_FIX_STREAM + round_idx), jj))(
                    jnp.arange(s)))(row_keys)
                fix = jax.vmap(jax.vmap(
                    lambda kk, rr: jax.random.categorical(
                        kk, jnp.where(rr > 0,
                                      jnp.log(jnp.maximum(rr, 1e-38)),
                                      NEG_INF))))(fkeys, res)
                corr = jnp.take_along_axis(
                    fix.astype(jnp.int32), n_acc[:, None], axis=1)[:, 0]
            else:
                g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                agree = (d == g[:, :k_spec])
                n_acc = jnp.cumprod(agree.astype(jnp.int32),
                                    axis=1).sum(axis=1)
                corr = jnp.take_along_axis(
                    g, n_acc[:, None], axis=1)[:, 0]

            # -- commit: positions 0..n are real (accepted prefix plus
            # the correction/bonus token); stop/budget freezes evolve
            # exactly as the per-step scan's --
            i_idx = jnp.arange(s)[None, :]
            d_pad = jnp.concatenate(
                [d, jnp.zeros((b, 1), jnp.int32)], axis=1)
            seq0 = jnp.where(i_idx < n_acc[:, None], d_pad,
                             corr[:, None])
            entry_done = done
            out_toks = []
            cur = tok
            count = jnp.zeros(b, jnp.int32)
            for i in range(s):
                live_pos = (~done) & (i <= n_acc)
                cur = jnp.where(live_pos, seq0[:, i], cur)
                idx = t_row + 1 + i          # global token index (B,)
                done = (done
                        | (live_pos & stop_gate(cur, idx + 1))
                        | (live_pos & (idx >= budget)))
                count = count + live_pos.astype(jnp.int32)
                out_toks.append(cur)
            toks_out = jnp.stack(out_toks, axis=1)              # (B, S)
            accepted = jnp.where(entry_done, 0, n_acc).astype(jnp.int32)
            return (caches, draft_caches, toks_out, count, cur, done,
                    accepted)

        def meshed(impl, name: str):
            """`impl` under use_mesh(mesh), to be jitted: tracing runs the
            body, so the KV hints of this mesh are baked into every
            compiled program (and the attributes stay jit objects —
            capture_program_cost .lower()s them).  Profiles and
            chip_smoke.py find a program by `name`."""
            def call(*args):
                with use_mesh(mesh):
                    return impl(*args)
            call.__name__ = name
            return call

        self._prefill = jax.jit(meshed(prefill_impl, "prefill_meshed"))
        self._segment = jax.jit(meshed(segment_impl, "segment_meshed"),
                                static_argnums=(0, 1))
        # The serving segment steps on the buffers it is handed: the
        # resident state is DONATED, so the step loop's carry is the
        # caller's own windows and no call copies them in (a parameter
        # that is not donated is read-only: XLA copies every window into
        # the carry first, 5.5 ms of a 1.3 B model's 8-row call).  The
        # caller's arrays are consumed: `serve_step` returns the state to
        # go on with.  A call that also GROWS the windows has no buffer
        # of the new width to take over, so it runs the same program
        # without donation (`serve_step` picks).  The offline `_segment`
        # is probed by `capture_program_cost` on the arguments it is then
        # called with, and keeps its arguments.
        serve_segment = meshed(serve_segment_impl, "serve_segment_meshed")
        self._serve_segment = jax.jit(serve_segment, static_argnums=(0, 1),
                                      donate_argnums=(3,))
        self._serve_segment_grows = jax.jit(serve_segment,
                                            static_argnums=(0, 1))
        self._prefill_chunk0 = jax.jit(
            meshed(prefill_chunk0_impl, "prefill_chunk0_meshed"),
            static_argnums=(0,))
        self._prefill_chunk = jax.jit(
            meshed(prefill_chunk_impl, "prefill_chunk_meshed"))
        self._prefill_finish = jax.jit(
            meshed(prefill_finish_impl, "prefill_finish_meshed"))
        self._resume_init = jax.jit(
            meshed(resume_init_impl, "resume_init_meshed"),
            static_argnums=(0,))
        if spec_tokens:
            self._draft_prefill = jax.jit(
                meshed(draft_prefill_impl, "draft_prefill_meshed"))
            self._spec_round = jax.jit(
                meshed(spec_round_impl, "spec_round_meshed"),
                static_argnums=(0,))
        self._programs: set = set()
        # a new class's `recompile` event carries what the building thread
        # compiled since this mark, not since the process began
        compiles.since_mark()
        self._program_costs: dict = {}  # program key -> captured cost row
        # (captured once at the recompile; replayed into every later
        # run_telemetry block so warm-engine runs still get roofline rows)
        self.last_segments_run = 0
        self.last_new_tokens_computed = 0
        self.last_exit_checks_skipped = 0
        self.last_spec_rounds = 0
        self.last_spec_drafted = 0
        self.last_spec_accepted = 0
        self.last_spec_acceptance = 0.0

    def bucket_for(self, prompt_len: int) -> int:
        return bucket_length(prompt_len, self.module.max_len,
                             self.max_new_tokens, self.min_bucket)

    def resident_variables(self, variables, draft: bool = False):
        """`variables` (the draft model's with `draft`) as this engine's
        programs should be handed them, before their placement on its
        mesh: the module-level `resident_variables`."""
        return resident_variables(
            self.draft_module if draft else self.module, variables,
            self.mesh)

    # -- serving hooks (serve/engine.py) ---------------------------------
    # The continuous-batching scheduler drives the engine's compiled
    # programs directly at segment granularity: prefill a join cohort,
    # splice its cache rows into the resident batch, advance everyone one
    # mixed-age segment, cancel/harvest at the boundary.  All three hooks
    # keep the jit shape-class discipline (and the recompile telemetry)
    # of the batch path.

    def _refuse_seq(self, hook: str) -> None:
        """Serving hooks refuse a seq-sharded engine: continuous
        batching's per-row cache writes, row splices, and prefix-cache
        handoff pages all assume whole-window rows on one device.  Use
        `generate()` / TextGenerator for seq-parallel long-context
        decode."""
        if self.seq_shards > 1:
            raise ValueError(
                f"{hook} does not support a seq-sharded engine (mesh "
                f"seq={self.seq_shards}): serving assumes whole-window "
                "cache rows; use DecodeEngine.generate / TextGenerator "
                "for seq-parallel long-context decode")

    def serve_prefill(self, variables, prompts, true_len, live, row_keys):
        """Prefill one join cohort: prompts (N, bucket) right-padded,
        per-row true lengths, `live=False` born-done pad rows, per-row
        sampling keys.  Returns (tok, done, caches) — the cohort's first
        generated token per row and its bucket-window caches, ready to
        splice into a resident batch with `merge_cache_rows`."""
        self._refuse_seq("serve_prefill")
        b, p = prompts.shape
        key = ("prefill", b, p)
        tok, done, caches, *self.counts_out = self._prefill(
            variables, jnp.asarray(prompts), jnp.asarray(true_len),
            jnp.asarray(live), row_keys)
        self._program(*key)
        self._count_relayout("prompt", caches, p)
        return tok, done, caches

    def serve_step(self, variables, caches, tok, done, true_len, budget,
                   bucket: int, t_row, row_keys, seg_len: int,
                   window: int):
        """Advance a mixed-age resident batch `seg_len` decode steps
        (models/generate.py serve_segment_impl): per-row step offsets
        `t_row` and per-row token budgets; returns (caches, toks
        (B, seg_len), tok, done).  `window` must cover the highest slot
        any live row writes: bucket + max(t_row) + seg_len, chunk-rounded
        (`serve_window`).  `caches` is CONSUMED where the window does not
        grow (the segment steps on those buffers in place): go on with
        the state returned."""
        self._refuse_seq("serve_step")
        b = int(tok.shape[0])
        w_in = self.state_window(caches)
        # a resident cache never shrinks: joins after long-running rows
        # completed can ask for a smaller cover than the batch already
        # holds — the segment then just attends the existing width
        window = max(int(window), w_in)
        key = ("serve_segment", b, w_in, window, seg_len)
        segment = (self._serve_segment if w_in == window
                   else self._serve_segment_grows)
        caches, toks, tok, done, *self.counts_out = segment(
            seg_len, window, variables, caches, tok, done,
            jnp.asarray(true_len), jnp.asarray(budget, jnp.int32),
            jnp.asarray(bucket, jnp.int32),
            jnp.asarray(t_row, jnp.int32), row_keys)
        self._program(*key)
        self._count_relayout("step", caches)
        self._count_row_writes(self._decoding, "step", caches, seg_len)
        return caches, toks, tok, done

    def _count_row_writes(self, decoding, program: str, state,
                          steps: int) -> None:
        """Add the per-row writes of the program just dispatched, as its
        decoding counts them, to `row_writes` / `row_writes_looped`."""
        writes, looped = decoding.row_writes(program, state, steps)
        self.row_writes += writes
        self.row_writes_looped += looped

    def _count_relayout(self, program: str, state, tokens: int = 0) -> None:
        """Add what the program just dispatched re-tiles of `state`, as
        the decoding says of it, to `relayout_bytes`."""
        self.relayout_bytes += self._decoding.relayout_bytes(
            program, state, tokens)

    def state_window(self, caches) -> int:
        """Slots a state's window layers hold now."""
        return _state_window(caches, self.state_kinds)

    def empty_state(self, rows: int, bucket: int, draft: bool = False):
        """Zero resident state for `rows` rows of a bucket's batch, at
        the bucket's first window (`draft`: the draft model's, always
        model-dtype K/V): the allocation the programs make, made for the
        serving engine's resident batch."""
        decoding = self._draft_decoding if draft else self._decoding
        return decoding.empty_state(rows, _round_up(bucket + 1, self.chunk),
                                    resident=True)

    def state_bytes(self, caches) -> dict:
        """Bytes a state holds in its window layers and in its fixed
        ones."""
        total = {WINDOW: 0, FIXED: 0}
        for kind, layer in zip(self.state_kinds, caches):
            total[kind] += sum(int(leaf.nbytes) for leaf in layer)
        return total

    def serve_window(self, bucket: int, max_t: int, seg_len: int) -> int:
        """The chunk-rounded cache window covering a segment whose oldest
        live row sits at step `max_t`, capped at the model's position
        budget (frozen rows past the cap clamp their writes in-window)."""
        need = min(bucket + max_t + seg_len, self.module.max_len)
        return _round_up(max(need, bucket + 1), self.chunk)

    def serve_prefill_chunks(self, bucket: int) -> int:
        """How many chunks a chunked prefill of this bucket runs (0 = the
        whole-prompt program applies: chunking off, bucket no larger than
        the chunk, or a bucket the chunk doesn't divide — buckets are
        powers of two, so any power-of-two `prefill_chunk` divides every
        bucket it's smaller than)."""
        cl = self.prefill_chunk
        if not cl or bucket <= cl or bucket % cl:
            return 0
        return bucket // cl

    def serve_prefill_chunk(self, variables, prompts, true_len,
                            index: int, state):
        """Run chunk `index` of a join cohort's chunked prefill; `state`
        is None for chunk 0, else the (caches, last_logits) carry the
        previous chunk returned.  The serving engine interleaves these
        calls with resident decode segments, so a long prompt never
        stalls running requests (serve/engine.py)."""
        self._refuse_seq("serve_prefill_chunk")
        prompts = np.asarray(prompts)
        b, p = prompts.shape
        cl = self.prefill_chunk
        w0 = _round_up(p + 1, self.chunk)
        tl = jnp.asarray(true_len)
        tokens = jnp.asarray(prompts[:, index * cl:(index + 1) * cl])
        if index == 0:
            out = self._prefill_chunk0(w0, variables, tokens, tl)
            self._program("prefill_chunk0", b, cl, w0)
        else:
            caches, last = state
            out = self._prefill_chunk(variables, tokens, caches, last, tl,
                                      jnp.asarray(index * cl, jnp.int32))
            self._program("prefill_chunk", b, cl, w0)
        self._count_relayout("chunk" if index else "prompt", out[0], cl)
        return self._keep_chunk_counts(out)

    def _keep_chunk_counts(self, out) -> tuple:
        """A chunk program's `(caches, last, *counts)`: the counts wait,
        summed on the device, for the prefill's finish (whose fetch brings
        them); the `(caches, last)` state goes on."""
        caches, last, *counts = out
        self._chunk_counts = ([a + b for a, b in zip(self._chunk_counts,
                                                     counts)]
                              if self._chunk_counts else counts)
        return caches, last

    def serve_prefill_finish(self, state, live, row_keys):
        """Close a chunked serve prefill: the same (tok, done, caches)
        contract as `serve_prefill`, ready for `merge_cache_rows`."""
        self._refuse_seq("serve_prefill_finish")
        caches, last = state
        b = int(last.shape[0])
        w0 = self.state_window(caches)
        tok, done, caches = self._prefill_finish(caches, last,
                                                 jnp.asarray(live),
                                                 row_keys)
        self._program("prefill_finish", b, w0)
        self.counts_out, self._chunk_counts = self._chunk_counts, []
        return tok, done, caches

    def serve_resume_chunks(self, bucket: int, prefix_len: int) -> int:
        """How many SUFFIX chunks a chunk-interleaved resume from a
        `prefix_len`-token donor prefix runs (0 = resume inline via
        `serve_prefill_resume`: chunking off for this bucket, or the
        prefix is not prefill_chunk-aligned)."""
        total = self.serve_prefill_chunks(bucket)
        cl = self.prefill_chunk
        if (not total or prefix_len <= 0 or prefix_len >= bucket
                or prefix_len % cl):
            return 0
        return total - prefix_len // cl

    def serve_resume_init(self, row_caches, bucket: int):
        """Open a resumed prefill from donor prefix rows (the prefix
        pool's spliced-together chunk payloads, slot width = matched
        prefix): dequantize/grow to the bucket window and zero the
        running logits — a (caches, last) state `serve_prefill_chunk`
        (index >= 1) and `serve_prefill_finish` continue verbatim."""
        self._refuse_seq("serve_resume_init")
        w0 = _round_up(bucket + 1, self.chunk)
        b = int(row_caches[0][0].shape[0])
        n = int(row_caches[0][0].shape[1])
        state = self._resume_init(w0, row_caches)
        self._program("resume_init", b, n, w0, len(row_caches[0]),
                      row_caches[0][0].ndim)
        self._count_relayout("reopen", row_caches)
        return state

    def serve_prefill_resume(self, variables, prompts, true_len,
                             prefix_len: int, row_caches, live, row_keys):
        """Prefill ONLY the novel suffix of a prompt whose first
        `prefix_len` tokens have donor cache rows (prefix pool hit):
        one `prefill_chunk` call at traced offset `prefix_len` over the
        whole suffix, then the standard finish.  The dense full-cache
        attention path makes the suffix forward attend the donor slots
        exactly as a fresh prefill would its own — byte-identical
        greedy outputs are the contract (model-dtype rows exact; int8
        rows carry the documented quantization caveat).  Same
        (tok, done, caches) contract as `serve_prefill`."""
        self._refuse_seq("serve_prefill_resume")
        prompts = np.asarray(prompts)
        b, p = prompts.shape
        if not 0 < prefix_len < p:
            raise ValueError(
                f"prefix_len ({prefix_len}) must be inside the bucket "
                f"({p})")
        caches, last = self.serve_resume_init(row_caches, p)
        w0 = self.state_window(caches)
        tokens = jnp.asarray(prompts[:, prefix_len:])
        state = self._keep_chunk_counts(self._prefill_chunk(
            variables, tokens, caches, last, jnp.asarray(true_len),
            jnp.asarray(prefix_len, jnp.int32)))
        self._program("prefill_chunk", b, p - prefix_len, w0)
        self._count_relayout("chunk", state[0])
        return self.serve_prefill_finish(state, live, row_keys)

    def serve_draft_prefill(self, draft_variables, prompts):
        """Prefill the draft model's cache for a join cohort (speculative
        serving): returns the draft caches to splice alongside the target
        caches (`merge_cache_rows` handles both)."""
        self._refuse_seq("serve_draft_prefill")
        prompts = np.asarray(prompts)
        b, p = prompts.shape
        caches = self._draft_prefill(draft_variables,
                                     jnp.asarray(prompts))
        self._program("draft_prefill", b, p)
        return caches

    def serve_spec_round(self, variables, draft_variables, caches,
                         draft_caches, tok, done, true_len, budget,
                         bucket: int, t_row, round_idx: int, row_keys,
                         window: int):
        """One speculative round over the resident batch — the SAME
        compiled program as the batch path (per-row step offsets and
        budgets from the start).  Returns (caches, draft_caches, toks
        (B, k+1), counts, tok, done, accepted); the engine advances each
        row's t_row by its count and emits the counted prefix."""
        self._refuse_seq("serve_spec_round")
        b = int(tok.shape[0])
        w_in = int(caches[0][0].shape[1])
        window = max(int(window), w_in,
                     int(draft_caches[0][0].shape[1]))
        key = ("spec_round", b, w_in, window, self.spec_tokens)
        out = self._spec_round(
            window, variables, draft_variables, caches, draft_caches,
            tok, done, jnp.asarray(true_len),
            jnp.asarray(budget, jnp.int32),
            jnp.asarray(bucket, jnp.int32),
            jnp.asarray(t_row, jnp.int32),
            jnp.asarray(round_idx, jnp.int32), row_keys)
        self._program(*key)
        # the draft's k+1 single-token steps, the target's one verify
        # segment of k+1 slots a row
        self._count_row_writes(self._draft_decoding, "step", out[1],
                               self.spec_tokens + 1)
        self._count_row_writes(self._decoding, "verify", out[0],
                               self.spec_tokens + 1)
        return out

    @staticmethod
    def merge_cache_rows(dst_caches, src_caches, dst_rows, src_rows,
                         mesh=None, kinds=None):
        """Splice cohort cache rows into a resident batch: row
        `src_rows[i]` of `src_caches` replaces row `dst_rows[i]` of
        `dst_caches`.  Both sides are grown to the wider window first
        (zero-pad, `_grow_cache`), so a freshly prefilled cohort joins a
        long-running batch without recompiling anything.  Works for both
        cache layouts (2-tuple model-dtype, 4-tuple int8): every leaf is
        row-indexed on axis 0; `kinds` (an engine's `.state_kinds`) names
        the layers whose leaves are FIXED and so are row-indexed only.
        One jitted program per (windows, rows)
        shape class — a join is a handful of fused scatters, not a
        cascade of eager ops.  Pass `mesh` (an engine's `.mesh`) so the
        merge program's KV hints trace against it — sharded resident
        caches then stay sharded through every join."""
        if mesh is not None and int(mesh.shape.get(SEQ_AXIS, 1)) > 1:
            raise ValueError(
                "merge_cache_rows refuses seq-sharded caches (mesh "
                "seq>1): row splicing assumes whole-window rows; gather "
                "a row explicitly (serialize_cache_row np.asarray-"
                "gathers the window) or decode outside the serving join "
                "path")
        layout = lambda caches: [[(c.ndim, str(c.dtype)) for c in layer]
                                 for layer in caches]
        if layout(dst_caches) != layout(src_caches):
            # e.g. a handoff page of a folding engine (payloads (B, W,
            # H*D)) given to one that keeps (B, W, H, D), or model-dtype
            # rows to an int8 batch: never scattered across layouts
            raise ValueError(
                "merge_cache_rows: the source rows' state layout "
                f"(rank, dtype a leaf: {layout(src_caches)[0]}) is not "
                f"the resident batch's ({layout(dst_caches)[0]}): they "
                "come from an engine built otherwise (cache_dtype, mesh "
                "or speculation decide the resident layout); build both "
                "alike, or resume the rows through serve_prefill_resume, "
                "whose reopen_prompt converts them")
        di = jnp.asarray(dst_rows, jnp.int32)
        si = jnp.asarray(src_rows, jnp.int32)
        with use_mesh(mesh):
            return _merge_cache_rows_jit(dst_caches, src_caches, di, si,
                                         kinds=kinds)

    @property
    def compiled_programs(self) -> int:
        """Distinct compiled shape classes (prefill + segment) so far —
        mirrors jit's specialization key, so it counts real XLA programs."""
        return len(self._programs)

    def _program(self, *key) -> None:
        """Register one executed shape class; a NEW class is a recompile
        and surfaces as a telemetry `compile` event (zero-cost inactive)."""
        if key not in self._programs:
            self._programs.add(key)
            # with what the class cost: the compile ledger's rows that
            # this thread closed since its last new class (the call that
            # traced, lowered and compiled or loaded it has returned)
            cost = compiles.since_mark()
            trace_event("recompile", cat="compile", where="decode",
                        program=str(key), programs=int(cost["programs"]),
                        trace_s=round(cost["trace_s"], 4),
                        lower_s=round(cost["lower_s"], 4),
                        backend_s=round(cost["backend_s"]
                                        + cost["cache_load_s"], 4),
                        cache_hits=int(cost["cache_hits"]))

    def _run_chunked_prefill(self, variables, prompts, true_len, live,
                             row_keys):
        """Host loop for a CHUNKED prefill: chunk 0 allocates, later
        chunks share one compiled program (traced offset), finish
        samples and (int8) quantizes.  Same (tok, done, caches) contract
        — and the same first token — as the whole-prompt program."""
        prompts = jnp.asarray(prompts)
        b, p = int(prompts.shape[0]), int(prompts.shape[1])
        cl = self.prefill_chunk
        w0 = _round_up(p + 1, self.chunk)
        tl = jnp.asarray(true_len)
        with trace_span("decode.prefill_chunk", cat="bucket", bucket=p,
                        batch=b, chunk=cl, index=0):
            caches, last, *_ = self._prefill_chunk0(
                w0, variables, prompts[:, :cl], tl)
        self._program("prefill_chunk0", b, cl, w0)
        for ci in range(1, p // cl):
            with trace_span("decode.prefill_chunk", cat="bucket",
                            bucket=p, batch=b, chunk=cl, index=ci):
                caches, last, *_ = self._prefill_chunk(
                    variables, prompts[:, ci * cl:(ci + 1) * cl],
                    caches, last, tl, jnp.asarray(ci * cl, jnp.int32))
            self._program("prefill_chunk", b, cl, w0)
        tok, done, caches = self._prefill_finish(
            caches, last, jnp.asarray(live), row_keys)
        self._program("prefill_finish", b, w0)
        return tok, done, caches

    def _chunks_prefill(self, bucket: int) -> bool:
        return self.serve_prefill_chunks(bucket) > 0

    def generate(self, variables, prompts, true_len, *, rng=None,
                 row_ids=None, live=None,
                 draft_variables=None) -> np.ndarray:
        """Generate `max_new_tokens` per row: prompts (B, bucket) int32
        right-padded, true_len (B,) per-row prompt lengths.  Returns the
        GENERATED region (B, max_new_tokens) — after a row's first stop
        token the remaining slots repeat that token (and once every live
        row has stopped, the remaining segments are skipped entirely).

        `row_ids` is the stable per-row sampling-stream id (defaults to
        0..B-1); `live=False` rows (mesh shard padding) are born done so
        they never hold the batch open.  Arrays may be host numpy or
        already-placed device arrays (the mesh path shards them first).
        With `spec_tokens` set, `draft_variables` is required and decode
        runs draft/verify rounds instead of per-token segments — greedy
        outputs are byte-identical to the non-speculative path
        (test-pinned); sampled outputs draw from the same target
        distribution through rejection sampling, on disjoint RNG
        streams.
        """
        b, p = np.shape(prompts)[0], np.shape(prompts)[1]
        tl_host = np.asarray(true_len)
        if int(tl_host.max()) > p:
            raise ValueError(
                f"true_len ({int(tl_host.max())}) exceeds the prompt "
                f"bucket width ({p})")
        if int(tl_host.max()) + self.max_new_tokens > self.module.max_len:
            raise ValueError(
                f"prompt_len ({int(tl_host.max())}) + max_new_tokens "
                f"({self.max_new_tokens}) exceeds the model's max_len "
                f"({self.module.max_len})")
        if p % self.seq_shards:
            raise ValueError(
                f"prompt bucket ({p}) must divide by the mesh seq axis "
                f"({self.seq_shards}) for distributed blockwise prefill "
                "(pad the bucket — true_len already handles the tail)")
        base = rng if rng is not None else jax.random.key(0)
        ids = jnp.arange(b) if row_ids is None else jnp.asarray(row_ids)
        row_keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(ids)
        if live is None:
            live = np.ones(b, bool)
        timings = active_timings()
        run = active_run()
        if self.spec_tokens:
            if draft_variables is None:
                raise ValueError(
                    "this engine speculates (spec_tokens "
                    f"{self.spec_tokens}); generate() needs "
                    "draft_variables")
            return self._generate_speculative(
                variables, draft_variables, prompts, true_len, live,
                row_keys, b, p, timings, run)
        with trace_span("decode.generate", cat="phase", bucket=p, batch=b,
                        max_new_tokens=self.max_new_tokens):
            pf_key = ("prefill", b, p)
            pf_args = (variables, jnp.asarray(prompts),
                       jnp.asarray(true_len), jnp.asarray(live), row_keys)
            if self._chunks_prefill(p):
                with span_on(timings, "prefill"), \
                        trace_span("decode.prefill", cat="bucket",
                                   bucket=p, batch=b, chunked=True):
                    tok, done, caches = self._run_chunked_prefill(
                        variables, prompts, true_len, live, row_keys)
                    if timings is not None:
                        jax.block_until_ready(tok)
                psp = None
            else:
                if run is not None and pf_key not in self._programs:
                    # compile-time cost capture (observe/costmodel.py):
                    # once per program, with a synced probe execution —
                    # the live span below walls only the async dispatch
                    rec = capture_program_cost(self._prefill, pf_args,
                                               where="decode",
                                               program=pf_key,
                                               run=run, probe=True)
                    if rec is not None:
                        self._program_costs[pf_key] = rec
                with span_on(timings, "prefill"), \
                        trace_span("decode.prefill", cat="bucket",
                                   bucket=p, batch=b) as psp:
                    tok, done, caches, *_ = self._prefill(*pf_args)
                    if timings is not None:
                        jax.block_until_ready(tok)
                self._program(*pf_key)
            if run is not None and psp is not None:
                # replay the remembered cost row so warm-engine runs (no
                # recompile) still get roofline rows (idempotent)
                if pf_key in self._program_costs:
                    run.record_program_cost("decode", pf_key,
                                            self._program_costs[pf_key])
                run.add_program_time("decode", pf_key, psp.elapsed(),
                                     basis="dispatch")
            segs = decode_segments(p, self.max_new_tokens, self.chunk)
            check_exit = bool(self.stop_tokens)
            prev_w = _round_up(p + 1, self.chunk)
            parts = []
            segments_run = 0
            exit_checks_skipped = 0
            with span_on(timings, "decode"):
                for t0, seg_len, window in segs:
                    if check_exit and t0 + 1 < self.min_new_tokens:
                        # tokens 0..t0 exist, and a stop only freezes
                        # from token index min_new_tokens-1 on — no row
                        # can possibly be done, so skip the device->host
                        # sync outright (counted; gauge below)
                        exit_checks_skipped += 1
                    elif check_exit and bool(
                            np.asarray(jax.device_get(done)).all()):
                        trace_event("decode.early_exit", cat="decode",
                                    at_step=t0, batch=b,
                                    segments_skipped=len(segs)
                                    - segments_run)
                        break
                    seg_key = ("segment", b, prev_w, window, seg_len)
                    seg_args = (seg_len, window, variables, caches, tok,
                                done, jnp.asarray(true_len),
                                jnp.asarray(p, jnp.int32),
                                jnp.asarray(t0, jnp.int32), row_keys)
                    if run is not None and seg_key not in self._programs:
                        # captured BEFORE the call: the caches are
                        # rebound to window-grown outputs after it
                        rec = capture_program_cost(self._segment, seg_args,
                                                   where="decode",
                                                   program=seg_key, run=run,
                                                   probe=True,
                                                   static_argnums=(0, 1))
                        if rec is not None:
                            self._program_costs[seg_key] = rec
                    # occupancy: cache slots live after this segment over
                    # the slots the compiled step actually attends
                    with trace_span("decode.segment", cat="segment",
                                    window=window, seg_len=seg_len,
                                    step_offset=t0,
                                    occupancy=round(
                                        (p + t0 + seg_len) / window, 3)) \
                            as ssp:
                        caches, toks, tok, done, *_ = self._segment(
                            *seg_args)
                    self._program(*seg_key)
                    if run is not None and ssp is not None:
                        if seg_key in self._program_costs:
                            run.record_program_cost(
                                "decode", seg_key,
                                self._program_costs[seg_key])
                        run.add_program_time("decode", seg_key,
                                             ssp.elapsed(),
                                             basis="dispatch")
                    prev_w = window
                    parts.append(toks)
                    segments_run += 1
                generated = np.concatenate(
                    [np.asarray(x) for x in parts]
                    + [np.asarray(tok)[:, None]], axis=1)
        if run is not None:
            run.gauge("decode.compiled_programs", self.compiled_programs)
            run.gauge("decode.early_exit_checks_skipped",
                      exit_checks_skipped)
        self.last_segments_run = segments_run
        self.last_new_tokens_computed = generated.shape[1]
        self.last_exit_checks_skipped = exit_checks_skipped
        if generated.shape[1] < self.max_new_tokens:
            # early exit: every row is frozen on its stop token — the fill
            # is exactly what the skipped segments would have emitted
            fill = np.repeat(np.asarray(tok)[:, None],
                             self.max_new_tokens - generated.shape[1], axis=1)
            generated = np.concatenate([generated, fill], axis=1)
        return generated.astype(np.int32)

    def _generate_speculative(self, variables, draft_variables, prompts,
                              true_len, live, row_keys, b, p, timings,
                              run) -> np.ndarray:
        """The speculative form of `generate`: target prefill (chunked or
        whole — the same programs), a draft prefill, then draft/verify
        rounds until every row freezes or fills its budget.  One round
        program serves every round; the cache window grows with the
        oldest row exactly as the serve path's does."""
        k = self.spec_tokens
        max_new = self.max_new_tokens
        with trace_span("decode.generate", cat="phase", bucket=p,
                        batch=b, max_new_tokens=max_new, spec_tokens=k):
            with span_on(timings, "prefill"), \
                    trace_span("decode.prefill", cat="bucket", bucket=p,
                               batch=b, speculative=True):
                if self._chunks_prefill(p):
                    tok, done, caches = self._run_chunked_prefill(
                        variables, prompts, true_len, live, row_keys)
                else:
                    tok, done, caches = self._prefill(
                        variables, jnp.asarray(prompts),
                        jnp.asarray(true_len), jnp.asarray(live),
                        row_keys)
                    self._program("prefill", b, p)
                dcaches = self._draft_prefill(draft_variables,
                                              jnp.asarray(prompts))
                self._program("draft_prefill", b, p)
                if timings is not None:
                    jax.block_until_ready(tok)
            out = np.zeros((b, max_new), np.int32)
            out[:, 0] = np.asarray(tok)
            emitted = np.ones(b, np.int64)
            t_row_h = np.zeros(b, np.int32)
            # freeze once a row's newest token index reaches max_new-1 —
            # the per-step scan's budget semantics (serve_segment_impl)
            budget = jnp.full(b, max_new - 1, jnp.int32)
            tl_dev = jnp.asarray(true_len)
            bucket_dev = jnp.asarray(p, jnp.int32)
            done_h = np.asarray(jax.device_get(done))
            drafted = 0
            accepted_total = 0
            rounds = 0
            with span_on(timings, "decode"):
                while not bool(done_h.all()):
                    w_in = int(caches[0][0].shape[1])
                    window = max(
                        self.serve_window(p, int(t_row_h.max()), k + 1),
                        w_in, int(dcaches[0][0].shape[1]))
                    key = ("spec_round", b, w_in, window, k)
                    with trace_span("decode.spec_round", cat="segment",
                                    window=window, round=rounds):
                        (caches, dcaches, toks, counts, tok, done,
                         acc) = self._spec_round(
                            window, variables, draft_variables, caches,
                            dcaches, tok, done, tl_dev, budget,
                            bucket_dev, jnp.asarray(t_row_h),
                            jnp.asarray(rounds, jnp.int32), row_keys)
                    self._program(*key)
                    toks_h = np.asarray(toks)
                    counts_h = np.asarray(counts)
                    live_rows = counts_h > 0
                    drafted += int(live_rows.sum()) * k
                    accepted_total += int(np.asarray(acc).sum())
                    for r in np.nonzero(live_rows)[0]:
                        take = min(int(counts_h[r]),
                                   max_new - int(emitted[r]))
                        if take > 0:
                            out[r, emitted[r]:emitted[r] + take] = \
                                toks_h[r, :take]
                            emitted[r] += take
                    t_row_h = t_row_h + counts_h.astype(np.int32)
                    done_h = np.asarray(done)
                    rounds += 1
            tok_h = np.asarray(tok)
            for r in range(b):
                # rows frozen early repeat their stop token, exactly as
                # the non-speculative fill does
                if emitted[r] < max_new:
                    out[r, int(emitted[r]):] = tok_h[r]
        rate = accepted_total / drafted if drafted else 0.0
        self.last_spec_rounds = rounds
        self.last_spec_drafted = drafted
        self.last_spec_accepted = accepted_total
        self.last_spec_acceptance = rate
        self.last_segments_run = rounds
        self.last_new_tokens_computed = int(emitted.max()) if b else 0
        # process counters surface on /metrics as _total series even with
        # no run active; the gauges ride run_summary AND the Prometheus
        # exposition (observe/export.py renders live-run gauges)
        from mmlspark_tpu.observe.metrics import inc_counter
        inc_counter("decode.spec_drafted_tokens", drafted)
        inc_counter("decode.spec_accepted_tokens", accepted_total)
        if run is not None:
            run.gauge("decode.compiled_programs", self.compiled_programs)
            run.gauge("decode.spec_acceptance_rate", round(rate, 4))
            run.gauge("decode.spec_rounds", rounds)
        return out


class TextGenerator(Transformer):
    """Pipeline Transformer: a token-prompt column in, a generated-token
    column out — the LM counterpart of TPUModel's scoring loop.

    Rows are grouped by prompt BUCKET (next power of two — a handful of
    compiled shape classes scoring full batches, the same static-shape
    discipline as vision/transformer.py's ragged grouping, now shared
    across prompt lengths) and decoded through the `DecodeEngine`:
    bucketed prefill, cache-windowed segments, stop-token early exit.
    Output rows align with input rows.  Greedy tokens are exactly those
    of per-length decoding (engine contract); sampled rows draw from a
    per-row stream keyed on (seed, row position), so a row's sample never
    depends on which rows share its table or batch.

    With `stopTokens` set, each output row is trimmed after its first
    stop token (the stop token is kept), and a batch whose rows have all
    stopped exits decode early.  `beamWidth > 0` routes through the
    full-cache per-length beam program instead (windowing lands
    sampler-first — docs/performance.md).

    MoE models: each decode step routes its batch as one capacity-limited
    group, so a row's generations can depend on which rows share its
    batch (dense models are row-independent) — see `_mlp`; bucket pad
    rows never enter the cache a real row attends, but under MoE they do
    join the step's capacity groups (the same coupling mesh zero-pad rows
    already have).
    """

    inputCol = Param(None, "column of int token-id prompt arrays",
                     ptype=str)
    outputCol = Param("generated", "output column (prompt + new tokens)",
                      ptype=str)
    maxNewTokens = Param(32, "tokens to generate per row", ptype=int,
                         validator=lambda v: v > 0)
    temperature = Param(0.0, "0 = greedy; > 0 samples with this "
                        "temperature", ptype=float,
                        validator=lambda v: v >= 0)
    topK = Param(0, "sample only among the k most probable tokens "
                 "(0 = off; ignored when greedy)", ptype=int,
                 validator=lambda v: v >= 0)
    topP = Param(1.0, "nucleus sampling: smallest probability mass to "
                 "sample within (1.0 = off; ignored when greedy)",
                 ptype=float, validator=lambda v: 0 < v <= 1)
    beamWidth = Param(0, "deterministic beam search width; each row "
                      "emits its best beam (0 = off; overrides "
                      "temperature/topK/topP; full-cache per-length "
                      "path)", ptype=int,
                      validator=lambda v: v >= 0)
    seed = Param(0, "sampling seed (ignored when greedy); each row's "
                 "stream also folds in its table position, so draws are "
                 "grouping-independent", ptype=int)
    stopTokens = Param(None, "token ids that end a row's generation: the "
                       "row is trimmed after its first stop token "
                       "(kept), and a batch whose rows have all stopped "
                       "exits decode early (None/empty = off; ignored "
                       "by beam search)", ptype=(list, tuple))
    cacheChunk = Param(DEFAULT_CACHE_CHUNK, "decode cache-window growth "
                       "granularity in slots: each compiled decode "
                       "segment attends only over the cache prefix "
                       "rounded up to this, so steady-step cost scales "
                       "with occupancy, not max_len", ptype=int,
                       validator=lambda v: v >= 1)
    kvCacheDtype = Param(None, "decode KV-cache storage dtype: 'int8' "
                         "stores the cache quantized per-head "
                         "(quantize-on-write; dequant inside the "
                         "attention read) so the steady step streams 1 "
                         "byte per cached element; None/'model' keeps "
                         "the module's own dtype.  Beam search ignores "
                         "this (full-cache model-dtype path)", ptype=str,
                         domain=("model", "int8"))
    minNewTokens = Param(1, "suppress stop tokens until a row has "
                         "generated this many tokens (including the "
                         "stop itself).  Until the floor is reachable "
                         "the engine also skips the between-segment "
                         "device->host early-exit syncs entirely "
                         "(decode.early_exit_checks_skipped gauge)",
                         ptype=int, validator=lambda v: v >= 1)
    specTokens = Param(0, "speculative decoding: tokens the draft model "
                       "proposes per verify round (0 = off; requires "
                       "set_draft_bundle).  Greedy outputs stay "
                       "byte-identical to non-speculative decoding; "
                       "sampled outputs draw from the same target "
                       "distribution via rejection sampling (different "
                       "RNG streams).  Acceptance rate lands on the "
                       "decode.spec_acceptance_rate gauge", ptype=int,
                       validator=lambda v: v >= 0)
    prefillChunk = Param(0, "chunked prefill: run prompt forwards in "
                         "chunks of this many tokens (0 = whole-prompt)."
                         "  Primarily a serving knob — serve/engine.py "
                         "interleaves chunks with resident decode "
                         "segments so long prompts don't stall running "
                         "requests; the batch path runs the same "
                         "programs", ptype=int,
                         validator=lambda v: v >= 0)

    def __init__(self, bundle: Optional["ModelBundle"] = None, **kwargs):
        super().__init__(**kwargs)
        self._bundle = bundle
        self._draft_bundle = None
        self._compiled: dict = {}
        self._mesh = None
        self._device_vars: dict = {}   # placed weights by mesh (None: off-mesh)
        self._draft_device_vars: dict = {}

    def set_bundle(self, bundle: "ModelBundle") -> "TextGenerator":
        self._bundle = bundle
        self._compiled.clear()
        self._device_vars = {}
        return self

    def set_draft_bundle(self, bundle) -> "TextGenerator":
        """The small LM `specTokens` speculation drafts with
        (zoo/speculative.py builds one from a target bundle).  Not
        persisted by save(): re-attach after load, exactly like a mesh."""
        self._draft_bundle = bundle
        self._compiled.clear()
        self._draft_device_vars = {}
        return self

    def set_mesh(self, mesh) -> "TextGenerator":
        """Generate data-parallel over a device mesh: prompt batches are
        sharded along the 'data' axis (zero-padded to whole shards via
        pad_to_multiple — the TPUModel batching discipline) and weights
        are placed once per mesh — replicated at mp=1, partition-rule
        sharded (heads/hidden on 'model', parallel/partition.py) when the
        mesh carries a model axis, with the KV cache following on its
        heads axis.  Dense decode is purely batch-
        parallel (no collectives in the scan; meshed output equals
        single-device output, test-pinned).  MoE decode routes each step
        cross-batch, so its dispatch spans the mesh AND the zero-pad
        rows join the capacity groups — one more instance of the MoE
        batch-composition coupling documented on this class."""
        self._mesh = mesh
        self._compiled.clear()
        self._device_vars = {}
        return self

    @property
    def bundle(self) -> Optional["ModelBundle"]:
        return self._bundle

    def _beam_fn_for(self, prompt_len: int):
        key = ("beam", prompt_len, self.maxNewTokens, self.beamWidth)
        if key not in self._compiled:
            beam_fn = make_beam_search_fn(
                self._bundle.module(), prompt_len, self.maxNewTokens,
                self.beamWidth)
            # the stage emits each row's BEST beam
            self._compiled[key] = lambda v, p, fn=beam_fn: fn(v, p)[0][:, 0]
        return self._compiled[key]

    def _engine_for(self) -> DecodeEngine:
        # greedy ignores the filters: normalize them out of the cache key
        # so flipping topK/topP at temperature 0 never rebuilds the engine
        sampling = self.temperature > 0
        top_k = (self.topK or None) if sampling else None
        top_p = self.topP if sampling and self.topP < 1.0 else None
        stops = tuple(int(t) for t in (self.stopTokens or ()))
        kv_dtype = self.kvCacheDtype or "model"
        spec = int(self.specTokens)
        if spec and self._draft_bundle is None:
            raise ValueError(
                "specTokens > 0 needs a draft model; call "
                "set_draft_bundle() (zoo/speculative.py builds one)")
        key = ("engine", self.maxNewTokens, self.temperature, top_k, top_p,
               stops, self.cacheChunk, kv_dtype, self.minNewTokens,
               self.prefillChunk or None, spec)
        if key not in self._compiled:
            self._compiled[key] = DecodeEngine(
                self._bundle.module(), self.maxNewTokens,
                temperature=self.temperature, top_k=top_k, top_p=top_p,
                stop_tokens=stops, chunk=self.cacheChunk,
                cache_dtype=kv_dtype, mesh=self._mesh,
                min_new_tokens=self.minNewTokens,
                prefill_chunk=self.prefillChunk or None,
                draft_module=(self._draft_bundle.module() if spec
                              else None),
                spec_tokens=spec)
        return self._compiled[key]

    def _device_variables(self):
        """Weights placed once per mesh (`bridge.place_weights`, the
        TPUModel discipline): off-mesh (key None) on the default device,
        replicated on a dp-only mesh, partition-rule sharded when the
        mesh has a model axis (the bundle's own rule set when it carries
        one, DEFAULT_RULES otherwise).  No jitted call is handed the
        bundle's host tree, which it would upload anew each time."""
        if self._mesh not in self._device_vars:
            from mmlspark_tpu.parallel.bridge import place_weights
            self._device_vars[self._mesh] = place_weights(
                resident_variables(self._bundle.module(),
                                   self._bundle.variables, self._mesh),
                self._mesh, self._bundle.partition_rules())
        return self._device_vars[self._mesh]

    def _draft_device_variables(self):
        """Draft weights, placed once per mesh like the target's but
        always whole on every device (the draft is small by design; its
        cache rides the data axis only — DRAFT_KV_CACHE_SPEC)."""
        if self._mesh not in self._draft_device_vars:
            from mmlspark_tpu.parallel.bridge import place_weights
            self._draft_device_vars[self._mesh] = place_weights(
                resident_variables(self._draft_bundle.module(),
                                   self._draft_bundle.variables, self._mesh),
                self._mesh, replicate_only=True)
        return self._draft_device_vars[self._mesh]

    def _transform_beam(self, rows: list, out: list) -> None:
        """Beam rows decode through the full-cache per-length programs."""
        by_len: dict[int, list[int]] = {}
        for i, r in enumerate(rows):
            by_len.setdefault(len(r), []).append(i)
        for plen, idxs in sorted(by_len.items()):
            fn = self._beam_fn_for(plen)
            prompts = np.stack([rows[i] for i in idxs])
            variables = self._device_variables()
            if self._mesh is not None:
                from mmlspark_tpu.parallel.bridge import (pad_to_multiple,
                                                          put_sharded)
                from mmlspark_tpu.parallel.mesh import batch_sharding
                data = self._mesh.shape["data"]
                prompts, _ = pad_to_multiple(prompts, data)
                # one straight-to-sharded transfer (no default-device hop)
                prompts = put_sharded(prompts, batch_sharding(self._mesh))
            else:
                prompts = jnp.asarray(prompts)
            with use_mesh(self._mesh):
                got = np.asarray(fn(variables, prompts))
            for j, i in enumerate(idxs):
                out[i] = got[j]

    def _transform_engine(self, rows: list, out: list) -> None:
        """Sampler/greedy rows decode through the bucketed engine."""
        engine = self._engine_for()
        n = len(rows)
        by_bucket: dict[int, list[int]] = {}
        for i, r in enumerate(rows):
            by_bucket.setdefault(engine.bucket_for(len(r)), []).append(i)
        base = jax.random.key(self.seed)
        stops = np.asarray(engine.stop_tokens, np.int32)
        for bucket, idxs in sorted(by_bucket.items()):
            b = len(idxs)
            prompts = np.zeros((b, bucket), np.int32)
            true_len = np.empty(b, np.int32)
            for j, i in enumerate(idxs):
                true_len[j] = len(rows[i])
                prompts[j, :true_len[j]] = rows[i]
            live = np.ones(b, bool)
            # the per-row sampling-stream id is the row's TABLE position:
            # stable under any grouping or batch composition
            row_ids = np.asarray(idxs, np.int32)
            variables = self._device_variables()
            if self._mesh is not None:
                from mmlspark_tpu.parallel.bridge import put_batch_parts
                data = self._mesh.shape["data"]
                pad = -(-b // data) * data - b
                if pad:
                    prompts = np.pad(prompts, ((0, pad), (0, 0)))
                    # pad rows: length-1 zero prompts, born not-live (the
                    # engine marks them done so they never hold the batch
                    # open), unique stream ids past the real rows
                    true_len = np.pad(true_len, (0, pad), constant_values=1)
                    live = np.pad(live, (0, pad))
                    row_ids = np.concatenate(
                        [row_ids, n + np.arange(pad, dtype=np.int32)])
                prompts, true_len, live = put_batch_parts(
                    self._mesh, prompts, true_len, live)
            draft_vars = (self._draft_device_variables()
                          if engine.spec_tokens else None)
            got = engine.generate(variables, prompts, true_len, rng=base,
                                  row_ids=row_ids, live=live,
                                  draft_variables=draft_vars)
            for j, i in enumerate(idxs):
                gen = got[j]
                if stops.size:
                    # stops before the minNewTokens floor were suppressed
                    # by the engine; don't trim at them either
                    start = max(int(self.minNewTokens) - 1, 0)
                    hits = np.isin(gen[start:], stops).nonzero()[0]
                    if hits.size:
                        gen = gen[:start + hits[0] + 1]
                out[i] = np.concatenate([rows[i], gen])

    def transform(self, table: "DataTable") -> "DataTable":
        self._check_required()
        if self._bundle is None:
            raise ValueError(
                "TextGenerator has no model bundle; call set_bundle()")
        col = table[self.inputCol]
        rows = [np.asarray(r, np.int32) for r in col]
        n = len(rows)
        out: list = [None] * n
        with trace_span("generate.transform", cat="phase", rows=n,
                        beam=self.beamWidth > 0):
            if self.beamWidth > 0:
                self._transform_beam(rows, out)
            else:
                self._transform_engine(rows, out)
        if n and len({len(r) for r in out}) == 1:
            return table.with_column(self.outputCol, np.stack(out))
        result = np.empty(n, object)
        for i, r in enumerate(out):
            result[i] = r
        return table.with_column(self.outputCol, result)

    def _save_extra(self, path: str) -> None:
        if self._bundle is not None:
            save_bundle(self._bundle, f"{path}/bundle")

    def _load_extra(self, path: str) -> None:
        import os
        self._bundle = (load_bundle(f"{path}/bundle")
                        if os.path.exists(f"{path}/bundle") else None)
        self._compiled = {}
        self._mesh = None
        self._device_vars = {}
        self._draft_bundle = None
        self._draft_device_vars = {}


def naive_generate(module, variables, prompts, max_new_tokens: int) -> np.ndarray:
    """Recompute-everything greedy decoding through the ordinary module
    forward — O(N * S^2) work, no cache.  The parity oracle for
    `generate`; never the product path."""
    _check_generatable(module, tuple(_DECODINGS), "naive_generate")
    toks = jnp.asarray(prompts, jnp.int32)
    for _ in range(max_new_tokens):
        logits = module.apply(variables, toks)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    return np.asarray(toks)
