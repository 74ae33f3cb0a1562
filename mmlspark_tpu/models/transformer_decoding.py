"""TransformerLM's decoding: the block and the stack, stated once as pure
functions over the flax param tree (models/definitions.py names: qkv /
proj / mlp_up / mlp_down / LayerNorm_0/1), so any trained TransformerLM
bundle, one trained through pipeline parallelism and converted back
included, generates without re-exporting weights.

A decode program differs from another only in how a layer's K and V meet
the rows' state: where a token's K/V is written and which function reads
the window.  That is an `attend(q, k, v, layer_state) -> (o, new
layer_state)` handed to the one stack; the views below are the six there
are.  `TransformerDecoding` is what `DecodeEngine` asks of the model
(`generate._decoding_for`), beside `hybrid_lm.HybridDecoding`.

Parity with recompute-everything decoding is pinned exactly at float32 by
tests/test_generate.py for prompts below PREFILL_FLASH_MIN (the flash
prefill's online softmax can reassociate near-tie logits above it), and
view by view by tests/test_decoding_seam.py.  One deliberate dtype
difference: decode attention accumulates QK^T / PV in float32 (the
single-query step is bandwidth-bound, so the extra precision is free),
while the training forward's einsums run in the model dtype; for bfloat16
bundles the logits agree to bf16 rounding (test-pinned), and near-tie
greedy choices may legitimately resolve differently.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from mmlspark_tpu.models.hybrid_lm import (NEG_INF, PREFILL_FLASH_MIN,
                                           WINDOW, Decoding, _row_write)
from mmlspark_tpu.parallel.mesh import SEQ_AXIS


def _ln(p: dict, x: jax.Array, dtype) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + 1e-6)
    return (y * p["scale"] + p["bias"]).astype(dtype)


def _dense(p: dict, x: jax.Array, dtype) -> jax.Array:
    if "kernel_scale" in p:
        # int8-quantized kernel (quant/quantize.py layout): int8 weights x
        # low-precision activations with the per-output-channel rescale
        # applied AFTER the matmul — same fused math as quant/modules.py,
        # so int8 TransformerLM bundles decode without a re-export
        y = (x.astype(dtype) @ p["kernel"].astype(dtype)).astype(jnp.float32)
        y = y * p["kernel_scale"] + p["bias"].astype(jnp.float32)
        return y.astype(dtype)
    return (x.astype(dtype) @ p["kernel"].astype(dtype)
            + p["bias"].astype(dtype))


# The dicts of a block that `_dense` reads (the head's, `lm_head`, sits
# beside the blocks): `resident_params` names their leaves from this.
_DENSE_DICTS = ("qkv", "proj", "mlp_up", "mlp_down")


def _mlp(module, bp: dict, h2: jax.Array, dtype) -> jax.Array:
    """The block's MLP half over normalized activations h2 (B, S, D).

    MoE blocks re-apply the REAL MoEMLP flax module against the block's
    own params, so routing math is never duplicated here
    (tests/test_decoding_seam.py holds every view to `module.apply`).
    Per-segment routing matches training semantics exactly at prefill
    (same token group, same capacity arithmetic).  Decode steps route
    the step's BATCH as one group, so under capacity pressure routing
    can diverge from the full-sequence recompute in either direction
    (keep a token it would drop, or drop one it would keep), and a
    row's generations can depend on its co-batched rows — the capacity
    drop is a batch-level construct a stepwise decoder cannot reproduce.
    Tests pin prefill parity exactly and greedy parity in the drop-free
    regime (moe_group_size=1)."""
    if module.mlp_impl == "moe":
        from mmlspark_tpu.ops.moe import MoEMLP
        return MoEMLP(module.d_model, n_experts=module.n_experts,
                      mlp_ratio=module.mlp_ratio, dtype=dtype,
                      expert_axis=module.expert_axis,
                      router_k=module.moe_router_k,
                      group_size=module.moe_group_size).apply(
            {"params": bp["moe"]}, h2)
    return _dense(bp["mlp_down"], jax.nn.gelu(
        _dense(bp["mlp_up"], h2, dtype)), dtype)


def _block(module, bp: dict, x: jax.Array, layer_state, attend, dtype):
    """One TransformerBlock over x (B, S, D); `attend` is the view."""
    b, s, d = x.shape
    h = _ln(bp["LayerNorm_0"], x, dtype)
    qkv = _dense(bp["qkv"], h, dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    shape = (b, s, module.n_heads, d // module.n_heads)
    q, k, v = (t.reshape(shape) for t in (q, k, v))
    o, layer_state = attend(q, k, v, layer_state)
    x = x + _dense(bp["proj"], o.reshape(b, s, d).astype(dtype), dtype)
    h2 = _ln(bp["LayerNorm_1"], x, dtype)
    return x + _mlp(module, bp, h2, dtype), layer_state


def _stack(module, params: dict, tokens: jax.Array, positions: jax.Array,
           state: list, attend):
    """The model over tokens (B, S) at `positions` ((S,), shared by the
    rows, or (B, S)), or over one token a row, both (B,): `(float32
    logits (B, S, V), new state)`.  Same dtype discipline as
    TransformerLM: the embeddings are summed in float32, final norm and
    head run in the compute dtype."""
    dtype = module.dtype
    emb = (params["tok_embed"]["embedding"][tokens]
           + params["pos_embed"]["embedding"][positions])
    if emb.ndim == 2:
        emb = emb[:, None]
    x = emb.astype(dtype)
    new_state = []
    for i in range(module.n_layers):
        x, layer_state = _block(module, params[f"block{i}_w"], x, state[i],
                                attend, dtype)
        new_state.append(layer_state)
    x = _ln(params["final_norm_w"], x, dtype)
    logits = _dense(params["lm_head"], x, dtype).astype(jnp.float32)
    return logits, new_state


# ---------------------------------------------------------------------------
# The views: what a layer's K and V do with the rows' state
# ---------------------------------------------------------------------------

def _window_entry(t: jax.Array, cache: jax.Array) -> jax.Array:
    """New K or V, (B, S, H, D), as slots of `cache`: in its dtype, and
    head-folded where the window is (`TransformerDecoding.folds`).  The
    fold of a token's own K/V undoes `_block`'s split into heads: it is
    the qkv product's column slice, so nothing is re-tiled for it."""
    return t.astype(cache.dtype).reshape(t.shape[:2] + cache.shape[2:])


def _cache_view(write, read):
    """The view of a decode step or a verify segment: `write(leaf,
    entries) -> leaf` puts the new slots into a leaf of the layer's
    state, `read(q, k, v, **scales) -> o` attends the written windows.
    A layer's state is (k, v) in the model dtype or, for an int8 cache,
    (k int8, k_scale, v int8, v_scale): new K/V are quantized per head
    ON WRITE and the read dequantizes inside the attention, so the
    steady step streams 1 byte a cached element."""
    def attend(q, k, v, layer):
        if len(layer) == 4:
            from mmlspark_tpu.quant.quantize import quantize_kv
            kq, ks, vq, vs = layer
            k8, k8s = quantize_kv(k)
            v8, v8s = quantize_kv(v)
            kq, ks = write(kq, k8), write(ks, k8s)
            vq, vs = write(vq, v8), write(vs, v8s)
            return (read(q, kq, vq, k_scale=ks, v_scale=vs),
                    (kq, ks, vq, vs))
        k_cache, v_cache = layer
        k_cache = write(k_cache, _window_entry(k, k_cache))
        v_cache = write(v_cache, _window_entry(v, v_cache))
        return read(q, k_cache, v_cache), (k_cache, v_cache)
    return attend


def _slot_write(slot):
    """Every row writes from the one `slot` on (decode slots sit after
    the bucket's pad tail; a prompt segment starts at its offset)."""
    return lambda c, e: lax.dynamic_update_slice(
        c, e, (0, slot) + (0,) * (c.ndim - 2))


def _masked_dense_read(q, k_cache, v_cache, pos):
    """softmax(q k^T) v in float32 against the whole (B, L, H, D) cache,
    under the global causal mask: the query at pos+i sees cache slots
    0..pos+i.  Its f32 softmax is bit-stable for the exact-parity tests.
    A head-folded cache (B, L, H*D) is unfolded for the read: the one
    place a prompt re-tiles a window (`relayout_bytes`)."""
    s, dh = q.shape[1], q.shape[3]
    k_cache, v_cache = (c.reshape(c.shape[:2] + q.shape[2:])
                        for c in (k_cache, v_cache))
    scores = jnp.einsum("bqhd,blhd->bhql", q.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * dh ** -0.5
    visible = (jnp.arange(k_cache.shape[1])[None, :]
               <= (pos + jnp.arange(s))[:, None])               # (S, L)
    w = jax.nn.softmax(jnp.where(visible[None, None], scores, NEG_INF),
                       axis=-1)
    return jnp.einsum("bhql,blhd->bqhd", w, v_cache.astype(jnp.float32))


def _segment_view(pos):
    """A token segment from slot `pos` on, against the whole cache, (B,
    L, H, D) or head-folded: a prefill (`pos` the static 0) or a prompt
    chunk or full-cache decode token (traced `pos`)."""
    write = _slot_write(pos)

    def attend(q, k, v, layer):
        state = tuple(write(c, _window_entry(t, c))
                      for c, t in zip(layer, (k, v)))
        if (q.shape[1] >= PREFILL_FLASH_MIN and isinstance(pos, int)
                and pos == 0):
            # long-prompt PREFILL ONLY (static pos 0: at decode, pos is a
            # tracer): attention against the cache is then exactly causal
            # self-attention over the segment, so the flash kernel
            # (O(block^2) memory, fwd-only) computes it without ever
            # materializing the (S, S) scores.  A long segment at pos > 0
            # would need the cached prefix too: it takes the dense read.
            # The kernel reads the segment's own K and V, never the
            # cache: a whole prompt re-tiles no window
            from mmlspark_tpu.ops.flash_attention import flash_attention
            return flash_attention(q, k, v, causal=True), state
        return _masked_dense_read(q, *state, pos), state
    return attend


def _ring_view(dtype):
    """The DISTRIBUTED blockwise prefill, inside the seq shard_map region
    with the LOCAL token slab (B, P/n): KV blocks rotate around `seq` by
    ppermute while each chip keeps only its slab's queries resident, so
    prefill FLOPs, activation memory and the O(P^2) score working set
    all scale ~1/n per chip.  Nothing is written: the slab's K and V ARE
    the local shard of the layer's seq-partitioned cache.
    `ring_attention` derives each block's global query positions from
    axis_index(seq) itself, so causal masking is globally correct over
    the rotating blocks; its output is f32 (online softmax)."""
    from mmlspark_tpu.ops.attention import ring_attention

    def attend(q, k, v, _):
        return (ring_attention(q, k, v, SEQ_AXIS, causal=True),
                (k.astype(dtype), v.astype(dtype)))
    return attend


def _step_view(write, visible, fused: bool):
    """One decode token a row: `write` is the step's write rule, the
    read is the single-query cache attention under the per-row `visible`
    mask (B, W): true-prompt slots plus decode slots written so far.
    `fused` reads through the Pallas single-query kernel
    (ops/decode_attention.py), which itself degrades to the XLA
    reference off-TPU or on shapes it can't tile, so tier-1 CPU runs
    exercise the fallback on the product path.  The engine only asks
    for it single-device: `pallas_call` carries no SPMD partitioning
    rule, so under a mesh the step keeps the einsum composition GSPMD
    can shard."""
    if fused:
        from mmlspark_tpu.ops.decode_attention import (
            fused_single_query_attention as attention)
    else:
        from mmlspark_tpu.ops.attention import (
            single_query_attention as attention)
    return _cache_view(write, lambda q, k, v, **scales: attention(
        q[:, 0], k, v, visible, **scales))


def _seq_step_view(slot, lo, w_l: int, visible):
    """A decode token against a SEQ-SHARDED window, inside the seq
    shard_map region.  Each chip holds a contiguous slab of `w_l` slots
    from its `lo = axis_index(seq) * w_l` on; the new K/V land on
    exactly the one chip that owns global `slot` (`owns` is a traced
    scalar — every chip computes the candidate write, the non-owners
    discard it via `jnp.where`, so no cross-chip writes ever happen).
    Reads become per-chip softmax STATS (f32 running (acc, m, l) against
    the local slab under the local slice of `visible`) merged across
    `seq`: one pmax + two psums per layer instead of gathering the
    window.  int8 dequant happens inside the local stats pass, before
    the merge."""
    from mmlspark_tpu.ops.attention import (merge_attention_stats,
                                            single_query_attention_stats)
    owns = (slot >= lo) & (slot < lo + w_l)
    local = _slot_write(jnp.clip(slot - lo, 0, w_l - 1))

    def read(q, k, v, **scales):
        acc, m, l = single_query_attention_stats(q[:, 0], k, v, visible,
                                                 **scales)
        return merge_attention_stats(acc, m, l, axis_name=SEQ_AXIS)
    return _cache_view(lambda c, e: jnp.where(owns, local(c, e), c), read)


def forward_with_cache(params: dict, tokens: jax.Array, caches: list,
                       pos, module):
    """Logits (B, S, V) for a token segment at `pos`, updating the whole
    (B, L, H, Dh) caches: prefill (S = prompt length, pos = 0), a prompt
    chunk, or a full-cache decode token (S = 1, traced pos) alike."""
    positions = pos + jnp.arange(tokens.shape[1])
    return _stack(module, params, tokens, positions, caches,
                  _segment_view(pos))


class TransformerDecoding(Decoding):
    """`Decoding` for a `TransformerLM`."""

    cache_dtypes = ("model", "int8")
    speculates = True
    shards = True

    def __init__(self, module, **how):
        super().__init__(module, **how)
        self.state_kinds = (WINDOW,) * module.n_layers
        # The RESIDENT LAYOUT.  The fused kernel reads a model-dtype
        # window (B, W, H, D) head-folded, as (B, W, H*D)
        # (`ops/decode_attention.py`).  On the TPU the two are tiled
        # differently, so the reshape is a copy of the window: inside the
        # step it was made for every layer's K and V at every decode step
        # (34 ms of a 126 ms segment of Cerebras-GPT-1.3B, PERF.md section
        # 6, PR 29), round the step loop at every segment call (11 ms of
        # 63, PR 33).  So where segments step through the fused kernel
        # the windows ARE head-folded, from `empty_state` on: a prompt
        # writes them so, `merge_cache_rows`, window growth, the prefix
        # pool and handoff pages carry them so (all rank-agnostic), and a
        # segment steps on them as they are.  What the engine knows when
        # it is built decides it: no mesh (`fused`), a model-dtype cache,
        # no speculation (`run_verify` reads (B, W, H, D)).
        self.folds = (self.fused and self.cache_dtype == "model"
                      and not self.verifies)

    def empty_state(self, rows: int, window: int,
                    resident: bool = False) -> list:
        """Zero K and V windows, one pair a layer, as a prompt is run
        into them: in the model dtype, hinted, head-folded where
        `folds`.  `resident`: as segments carry them (`close_prompt`'s
        layout) and with no hint, for a batch allocated outside any
        program."""
        m = self.module
        shape = (rows, window, m.n_heads, m.d_model // m.n_heads)
        if resident and self.cache_dtype == "int8":
            leaves = ((shape, jnp.int8), (shape[:3], jnp.float32)) * 2
        else:
            leaves = ((self._payload(shape), m.dtype),) * 2
        hint = (lambda c: c) if resident else self.hint
        return [tuple(hint(jnp.zeros(*leaf)) for leaf in leaves)
                for _ in range(m.n_layers)]

    def resident_params(self, params: dict, cast) -> dict:
        """`params` with `cast` over every leaf that the decode programs
        read ONLY through `_dense`'s `.astype(dtype)`: the kernel and
        bias of `_DENSE_DICTS` and of the head.  An int8 dict
        (`kernel_scale`) stays: its kernel is int8 and its bias is read
        in float32.  So do the LayerNorms (`_ln` works in float32), the
        two embeddings (summed in float32 before the cast) and a `moe`
        subtree (`MoEMLP` applies a float32 router to it).  A kernel
        that a new code path reads keeps to this rule or
        tests/test_resident_weights.py's jaxpr guard fails."""
        def dense(p: dict) -> dict:
            if "kernel_scale" in p:
                return p
            return {**p, "kernel": cast(p["kernel"]),
                    "bias": cast(p["bias"])}

        out = dict(params)
        out["lm_head"] = dense(params["lm_head"])
        for i in range(self.module.n_layers):
            name = f"block{i}_w"
            out[name] = {k: dense(v) if k in _DENSE_DICTS else v
                         for k, v in params[name].items()}
        return out

    def run_prompt(self, params, tokens, state, start, true_len, live):
        """A prompt segment of right-padded rows from slot `start` on (0
        for a whole prompt): `(float32 logits of every position (B, S,
        V), new state, counts)`.  Positions are the slots, shared by the
        rows: causal masking alone makes the per-row `true_len - 1`
        gather correct."""
        # `forward_with_cache`'s call, made here: the flash kernels then
        # trace a frame nearer the stack's base (PERF.md section 7)
        logits, state = _stack(
            self.module, params, tokens,
            start + jnp.arange(tokens.shape[1]), state, _segment_view(start))
        return logits, state, ()

    def head(self, params, last):
        # ROADMAP S2: `run_prompt` applies the head to every position, so
        # the gathered row is logits already.  Returning hidden states
        # there and applying `lm_head` here is S2's change, in these two
        # functions.
        return last

    def _payload(self, shape: tuple) -> tuple:
        """The shape of a model-dtype K or V leaf whose slots hold (H,
        D): as it is, or head-folded where `folds`."""
        return shape[:2] + (shape[2] * shape[3],) if self.folds else shape

    def _foreign(self, state: list) -> bool:
        """Model-dtype donor rows that an engine of the other layout
        made (a folding engine's rank 3 given to one that keeps (B, W,
        H, D), or the reverse)."""
        return any(len(layer) == 2 and (layer[0].ndim == 3) != self.folds
                   for layer in state)

    def close_prompt(self, state: list) -> list:
        """The state a finished prompt hands to segments.  A model-dtype
        prompt's state is resident as it was written (head-folded where
        `folds`); an int8 cache quantizes the whole prompt's K/V once
        here (decode steps quantize each new token on write), to (k
        int8, k_scale f32 (B, W, H), v int8, v_scale)."""
        if self.cache_dtype != "int8":
            return state
        from mmlspark_tpu.quant.quantize import quantize_kv
        return [tuple(self.hint(c)
                      for c in quantize_kv(kc) + quantize_kv(vc))
                for kc, vc in state]

    def reopen_prompt(self, state: list) -> list:
        """`close_prompt` undone, for a prompt resumed from donor rows,
        whichever engine closed them: int8 slots back in the model dtype
        (quantize_kv's round trip is idempotent, so closing again stores
        the same bytes), and every payload in THIS decoding's prompt
        layout, so donor rows of the other layout (`_foreign`) are
        re-tiled here once and never read wrong."""
        m = self.module
        dtype, heads = m.dtype, (m.n_heads, m.d_model // m.n_heads)
        reopened = []
        for layer in state:
            if len(layer) == 4:
                layer = tuple((q.astype(jnp.float32) * s[..., None])
                              .astype(dtype)
                              for q, s in (layer[:2], layer[2:]))
            reopened.append(tuple(
                c.reshape(self._payload(c.shape[:2] + heads))
                for c in layer))
        return reopened

    def relayout_bytes(self, program: str, state: list,
                       tokens: int = 0) -> int:
        """`Decoding.relayout_bytes`: a segment re-tiles nothing; a
        prompt segment on head-folded windows unfolds the rows' K and V
        where it takes the dense read (`_segment_view`: short of
        PREFILL_FLASH_MIN tokens, or a later chunk) and nothing where it
        takes the flash kernel; reopening re-tiles donor rows of the
        other layout."""
        if program == "reopen":
            retiles = self._foreign(state)
        else:
            retiles = self.folds and (program == "chunk" or (
                program == "prompt" and tokens < PREFILL_FLASH_MIN))
        if not retiles:
            return 0
        return sum(int(c.nbytes) for layer in state for c in layer)

    def _step_writes(self, state: list) -> list:
        """Every leaf once: K and V, and an int8 cache's two scales."""
        return [(leaf, 1) for layer in state for leaf in layer]

    def _run_step(self, params, tok, pos, state, attend):
        logits, state = _stack(self.module, params, tok, pos, state, attend)
        return logits[:, 0], state, ()

    def run_step(self, params, tok, pos, slot, state, visible, live):
        """One decode token a row, `(logits (B, V), new state, counts)`:
        per-row positions `pos` (true prompt length + step — NOT the
        cache slot), the write `slot` shared by the rows, per-row
        visibility (B, W)."""
        return self._run_step(params, tok, pos, state, _step_view(
            _slot_write(slot), visible, self.fused))

    def run_step_rows(self, params, tok, pos, slots, state, visible, live):
        """`run_step` with PER-ROW write `slots` (B,): the continuous
        batch, whose joined rows sit at different decode offsets.
        Callers clamp `pos` below max_len for frozen rows (their output
        is masked by `done`, but the position gather must stay in
        range); `dynamic_update_slice` clamps starts, so a frozen row
        whose slot has run past the window writes harmlessly into its
        own last slot."""
        return self._run_step(params, tok, pos, state, _step_view(
            lambda c, e: _row_write(c, e, slots), visible, self.fused))

    def run_verify(self, params, toks, pos0, slots0, state, visible):
        """Logits (B, S, V) for per-row contiguous S-token segments, the
        speculative target forward: ONE program scores every drafted
        position.  Row r's tokens sit at positions pos0[r].. (clamped to
        the position table) and write slots slots0[r]..slots0[r]+S-1 in
        one block; `visible` is per query (B, S, W).  At S = 1 the
        attention math is elementwise-identical to the single-query
        step: the property greedy byte-exactness under speculation rests
        on."""
        from mmlspark_tpu.ops.attention import segment_cache_attention
        positions = jnp.minimum(
            pos0[:, None] + jnp.arange(toks.shape[1])[None, :],
            self.module.max_len - 1)
        return _stack(self.module, params, toks, positions, state,
                      _cache_view(
                          lambda c, e: _row_write(c, e, slots0),
                          lambda q, k, v, **scales: segment_cache_attention(
                              q, k, v, visible, **scales)))

    def run_prompt_seq(self, params, tokens):
        """The local slab (B, P/n) of a whole prompt, inside the seq
        shard_map region: `(logits of the slab, its K and V a layer)`.
        Positions are the global slab offset on, `run_prompt`'s stream."""
        s_l = tokens.shape[1]
        positions = lax.axis_index(SEQ_AXIS) * s_l + jnp.arange(s_l)
        return _stack(self.module, params, tokens, positions,
                      [None] * self.module.n_layers,
                      _ring_view(self.module.dtype))

    def run_step_seq(self, params, tok, pos, slot, state, visible, live,
                     lo):
        """`run_step` inside the seq shard_map region: `slot` is global,
        `state` and `visible` cover the local slab from `lo` on.  The
        non-attention compute is replicated per seq shard —
        deterministic-identical on every chip, so the logits really are
        replicated over `seq` as the out_specs claim."""
        return self._run_step(params, tok, pos, state, _seq_step_view(
            slot, lo, state[0][0].shape[1], visible))
