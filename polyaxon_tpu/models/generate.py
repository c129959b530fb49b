"""Autoregressive generation with a per-layer KV cache.

The training side of the LM family lives in runtime/trainer.py; this is
the decode side: prompt prefill and token-by-token sampling through the
transformer's `decode=True` path (models/transformer.py Attention), where
each layer appends K/V into a cache variable and attends a single query
against the filled prefix — O(S) per token instead of O(S^2).

TPU-first shape discipline: ONE batched prefill forward (the whole prompt
at once, filling every layer's cache and sampling the first new token)
followed by ONE static-length `lax.scan` over the generated positions —
two compiled programs total, no per-token retrace, no dynamic shapes. An
optional `eos_id` freezes finished rows (they keep stepping but their
output is pinned, branch-free).

Usage:
    bundle = build_model("transformer_lm", {...})
    tokens = generate(bundle.module, params, prompt, max_new_tokens=32,
                      temperature=0.8, top_k=40, seed=0)
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .kv_pages import PagedKVLayout


def _top_k_mask(logits, top_k: Optional[int]):
    if top_k is not None and top_k > 0 and top_k < logits.shape[-1]:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits >= kth, logits, -1e30)
    return logits


def _sample(logits, rng, temperature: float, top_k: Optional[int]):
    """logits: [B, V] → [B] sampled token ids. temperature 0 = greedy."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = _top_k_mask(logits / temperature, top_k)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def _sample_rows(logits, rngs, temperature: float, top_k: Optional[int]):
    """Per-row keys: logits [B, V], rngs [B]-batched PRNG keys → [B] ids.

    The serving coalescer batches INDEPENDENT requests into one decode, so
    each row samples from its own request's key stream — coalescing must
    not correlate (or recompile over) client seeds."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = _top_k_mask(logits / temperature, top_k)
    return jax.vmap(jax.random.categorical)(rngs, logits).astype(jnp.int32)


def generate(
    module,
    params,
    prompt: jnp.ndarray,
    *,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    eos_id: Optional[int] = None,
    seed=0,  # int, or a traced int32 scalar (jit-friendly: shape-static fns
    # can take the seed as a runtime argument instead of recompiling per seed),
    # or a [B] array of per-row seeds (one independent stream per batch row)
    prompt_lengths=None,  # [B] true lengths of a LEFT-padded prompt batch
    adapter_ix=None,  # [B] per-row adapter slot (ISSUE 19): mixes tenants
    # in one batch on a slot-stacked model; None = base adapter (slot 0)
) -> jnp.ndarray:
    """Generate `max_new_tokens` continuations of `prompt` [B, P] (int32).

    Returns [B, P + max_new_tokens]. Prompt positions are teacher-forced
    (prefill runs through the same cached decode steps), sampling starts
    at position P. With `eos_id`, rows that emit it are padded with eos
    from then on. Total length is capped by the model's cfg.seq_len (the
    cache size).

    Shape bucketing (the serving fast path): with `prompt_lengths` [B],
    `prompt` is LEFT-padded to the shared width P and row b's true tokens
    occupy `prompt[b, P - prompt_lengths[b]:]`. Pad slots are masked out of
    attention and rotary positions are offset per row, so every true length
    in [1, P] shares ONE compiled program and row b's useful output is
    `out[b, P - prompt_lengths[b]:]` — identical to an unbucketed run of
    that row alone. With per-row seeds the sample stream is keyed by
    GENERATION index (not absolute position), so a row's tokens are also
    invariant to which bucket or batch it was coalesced into.
    """
    cfg = module.cfg
    B, P = prompt.shape
    total = P + int(max_new_tokens)
    if total > cfg.seq_len:
        raise ValueError(
            f"prompt ({P}) + max_new_tokens ({max_new_tokens}) = {total} "
            f"exceeds the model's seq_len {cfg.seq_len} (the KV cache size)"
        )
    prompt = prompt.astype(jnp.int32)
    pad = None
    pad_kw = {}
    if prompt_lengths is not None:
        pad = (P - jnp.asarray(prompt_lengths, jnp.int32)).astype(jnp.int32)
        pad_kw = {"pad": pad}  # only modules on the bucketed path take it
    if adapter_ix is not None:
        pad_kw["adapter_ix"] = jnp.asarray(adapter_ix, jnp.int32)

    # cache creation pass: one dummy mutable apply materializes zeroed
    # cache variables (flax recipe — variables appear on first mutable use)
    _, init_vars = module.apply(
        {"params": params},
        jnp.zeros((B, 1), jnp.int32),
        train=False,
        decode=True,
        mutable=["cache"],
    )
    # the creation pass fell through to full attention WITHOUT advancing
    # cache_index, so prefill below starts cleanly at position 0
    cache0 = init_vars["cache"]

    # batched prefill: the whole prompt in ONE forward that fills the
    # cache; its last-position logits sample the first new token (with
    # left-padding, position -1 is every row's last TRUE token)
    logits, vars1 = module.apply(
        {"params": params, "cache": cache0},
        prompt,
        train=False,
        decode=True,
        mutable=["cache"],
        **pad_kw,
    )
    per_row_seed = getattr(jnp.asarray(seed), "ndim", 0) == 1
    if per_row_seed:
        row_keys = jax.vmap(jax.random.PRNGKey)(
            jnp.asarray(seed, jnp.int32)
        )

        def step_rng(g):  # g = generation index, uniform across rows
            return jax.vmap(lambda k: jax.random.fold_in(k, g))(row_keys)

        sample = lambda lg, g: _sample_rows(lg, step_rng(g), temperature, top_k)  # noqa: E731
    else:
        rng0 = jax.random.PRNGKey(seed)
        # keyed by absolute buf position, as always (pinned by tests)
        sample = lambda lg, t: _sample(lg, jax.random.fold_in(rng0, t), temperature, top_k)  # noqa: E731
    first = sample(logits[:, -1].astype(jnp.float32), 0)

    buf = jnp.zeros((B, total), jnp.int32)
    buf = jax.lax.dynamic_update_slice(buf, prompt, (0, 0))
    buf = buf.at[:, P].set(first)

    def step(carry, t):  # t = position of the token being fed (>= P)
        cache, buf, done = carry
        tok = jax.lax.dynamic_slice(buf, (0, t), (B, 1))
        logits, out_vars = module.apply(
            {"params": params, "cache": cache},
            tok,
            train=False,
            decode=True,
            mutable=["cache"],
            **pad_kw,
        )
        # per-row streams key on generation index (t - P + 1): invariant to
        # the bucket's pad; the scalar stream keys on absolute position t
        nxt = sample(
            logits[:, -1].astype(jnp.float32),
            (t - P + 1) if per_row_seed else t,
        )
        if eos_id is not None:
            # latch only on GENERATED eos: the fed token at position >= P
            # is always model output; prompts legitimately contain eos as
            # separators and never enter this loop
            done = done | (tok[:, 0] == eos_id)
            nxt = jnp.where(done, eos_id, nxt)
        buf = jax.lax.dynamic_update_slice(buf, nxt[:, None], (0, t + 1))
        return (out_vars["cache"], buf, done), None

    done0 = jnp.zeros((B,), bool)
    if max_new_tokens > 1:
        (_, buf, _), _ = jax.lax.scan(
            step,
            (vars1["cache"], buf, done0),
            jnp.arange(P, total - 1),
        )
    return buf


# --------------------------------------------------------------- paged decode
# The block-paged pipeline (ISSUE 6): the KV cache is ONE pool of
# page-sized blocks shared by every in-flight request, indexed through
# per-row page tables, so serving admits against pool pages instead of
# reserving seq_len per row. Decode runs as prefill + fixed-size chunks
# (the serving layer allocates pages lazily between chunks and streams
# each chunk's tokens out), and the jit factories below DONATE the cache
# argument into each program, so the pool is updated in place — peak HBM
# never holds two copies across the prefill→decode handoff.
#
# Determinism contract: for the same per-row seeds/pads, the token
# sequence is byte-identical to the dense generate() path — same rope
# positions (slot - pad), same masked-softmax (dead slots underflow to
# exact 0.0 regardless of window width), same per-generation-index
# sample streams (tests/test_kv_pages.py pins this across the ladder).


def _row_rngs(row_keys, g):
    """Per-row sample keys for generation index `g` — the same fold the
    dense path uses, so coalescing/paging never changes a row's stream."""
    return jax.vmap(lambda k: jax.random.fold_in(k, g))(row_keys)


def _adapter_kw(adapter_ix):
    """kwargs for module.apply: the per-row adapter slots (ISSUE 19) only
    enter the call when a caller passes them, so every adapter-free
    program keeps its exact legacy trace."""
    if adapter_ix is None:
        return {}
    return {"adapter_ix": jnp.asarray(adapter_ix, jnp.int32)}


def make_paged_cache(module, params, layout: PagedKVLayout):
    """Materialize the pool-shaped cache pytree (zeros) via the standard
    creation apply. Leaves are [pool_pages, page_tokens, nkv, hd] (with a
    leading [n_layers] under scan_layers) — batch-size independent, so one
    pool serves every group shape."""
    _, init_vars = module.apply(
        {"params": params},
        jnp.zeros((1, 1), jnp.int32),
        train=False,
        decode=True,
        mutable=["cache"],
        pages=jnp.zeros((1, 1), jnp.int32),
        kv_layout=layout,
    )
    return init_vars["cache"]


def paged_prefill(
    module,
    params,
    cache,
    prompt: jnp.ndarray,
    *,
    pad,
    pages,
    kv_layout: PagedKVLayout,
    prefix_len: int,
    temperature: float,
    top_k: Optional[int],
    seeds,
    adapter_ix=None,
) -> tuple:
    """Prefill `prompt` [B, S] (LEFT-padded suffixes when a shared prefix
    of `prefix_len` tokens is already in the pool) through the page
    tables, starting at slot `prefix_len`, and sample the first new token
    per row (generation index 0). Returns (cache, first_tokens [B])."""
    logits, vars1 = module.apply(
        {"params": params, "cache": cache},
        prompt.astype(jnp.int32),
        train=False,
        decode=True,
        mutable=["cache"],
        pad=pad,
        pages=pages,
        pos=jnp.asarray(prefix_len, jnp.int32),
        kv_layout=kv_layout,
        prefix_len=prefix_len,
        **_adapter_kw(adapter_ix),
    )
    row_keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray(seeds, jnp.int32))
    first = _sample_rows(
        logits[:, -1].astype(jnp.float32),
        _row_rngs(row_keys, 0),
        temperature,
        top_k,
    )
    return vars1["cache"], first


def paged_decode_chunk(
    module,
    params,
    cache,
    tok,
    done,
    *,
    steps: int,
    pos,
    start_g,
    pad,
    pages,
    kv_layout: PagedKVLayout,
    prefix_len: int,
    temperature: float,
    top_k: Optional[int],
    eos_id: Optional[int],
    seeds,
    adapter_ix=None,
) -> tuple:
    """Run `steps` cached decode steps through the page table.

    `tok` [B] is the previously sampled (not yet fed) token, written at
    slot `pos`; `start_g` is the generation index of the FIRST token this
    chunk samples; `done` [B] carries the eos latch between chunks.
    Returns (cache, toks [B, steps], done) — eos semantics identical to
    generate(): done latches when a GENERATED eos is fed, later samples
    are pinned to eos."""
    row_keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray(seeds, jnp.int32))
    pos = jnp.asarray(pos, jnp.int32)
    start_g = jnp.asarray(start_g, jnp.int32)

    def step(carry, i):
        cache, tok, done = carry
        logits, out_vars = module.apply(
            {"params": params, "cache": cache},
            tok[:, None],
            train=False,
            decode=True,
            mutable=["cache"],
            pad=pad,
            pages=pages,
            pos=pos + i,
            kv_layout=kv_layout,
            prefix_len=prefix_len,
            **_adapter_kw(adapter_ix),
        )
        nxt = _sample_rows(
            logits[:, -1].astype(jnp.float32),
            _row_rngs(row_keys, start_g + i),
            temperature,
            top_k,
        )
        if eos_id is not None:
            done = done | (tok == eos_id)
            nxt = jnp.where(done, eos_id, nxt)
        return (out_vars["cache"], nxt, done), nxt

    (cache, _, done), toks = jax.lax.scan(
        step,
        (cache, jnp.asarray(tok, jnp.int32), done),
        jnp.arange(int(steps)),
    )
    return cache, toks.T, done


def jit_paged_prefill(
    module,
    *,
    kv_layout: PagedKVLayout,
    prefix_len: int,
    temperature: float,
    top_k: Optional[int],
):
    """Compiled prefill: (params, cache, prompt, pad, pages, seeds) →
    (cache', first). The cache argument is DONATED — the pool is updated
    in place, never duplicated (on backends without donation support,
    e.g. CPU, jax falls back to a copy with a warning)."""

    def prefill_paged(params, cache, prompt, pad, pages, seeds,
                      adapter_ix=None):
        return paged_prefill(
            module, params, cache, prompt,
            pad=pad, pages=pages, kv_layout=kv_layout,
            prefix_len=prefix_len, temperature=temperature, top_k=top_k,
            seeds=seeds, adapter_ix=adapter_ix,
        )

    return jax.jit(prefill_paged, donate_argnums=(1,))


def jit_paged_chunk(
    module,
    *,
    steps: int,
    kv_layout: PagedKVLayout,
    prefix_len: int,
    temperature: float,
    top_k: Optional[int],
    eos_id: Optional[int],
):
    """Compiled decode chunk: (params, cache, tok, done, pad, pages,
    seeds, pos, start_g) → (cache', toks [B, steps], done'). Cache is
    DONATED (see jit_paged_prefill); pos/start_g are traced scalars so
    successive chunks reuse one compile."""

    def decode_chunk_paged(params, cache, tok, done, pad, pages, seeds, pos,
                           start_g, adapter_ix=None):
        return paged_decode_chunk(
            module, params, cache, tok, done,
            steps=steps, pos=pos, start_g=start_g, pad=pad, pages=pages,
            kv_layout=kv_layout, prefix_len=prefix_len,
            temperature=temperature, top_k=top_k, eos_id=eos_id,
            seeds=seeds, adapter_ix=adapter_ix,
        )

    return jax.jit(decode_chunk_paged, donate_argnums=(1,))


# ----------------------------------------------------- chunked prefill (ISSUE 14)
# The step scheduler slices a row's prefill into `prefill_chunk_tokens`-
# sized pieces and interleaves them with ongoing decode steps, so a long
# prompt never monopolizes the decode worker. Two additional programs:
#
#  * `jit_paged_prefill_chunk` — one slice of the LEFT-padded suffix
#    written through the page table at a traced start slot. The FINAL
#    slice's last-position logits sample the first token exactly like
#    one-shot `jit_paged_prefill` (same fold_in(key, 0) stream), so the
#    prefill boundary is byte-identical however the prompt was sliced.
#  * `jit_paged_step` — ONE decode step for a batch of rows at per-row
#    write frontiers / generation indices / prefix widths. Rows that
#    joined the batch mid-flight (continuous batching) sample from their
#    own fold_in(key, g) streams, so batch composition never changes a
#    row's tokens.
#
# Both take the prefix width as a traced [B] argument (`prefix_lens`)
# instead of a compile-time constant: rows with different cached-prefix
# lengths share one compiled program, which is what lets arbitrary rows
# pack into one step. COW safety is inherited from the paged layout —
# chunk writes only ever target slots >= the row's prefix width, so
# shared prefix pages stay read-only.


def paged_prefill_chunk(
    module,
    params,
    cache,
    chunk: jnp.ndarray,
    *,
    pad,
    pages,
    kv_layout: PagedKVLayout,
    prefix_lens,
    pos,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    seeds=None,
    final: bool = False,
    adapter_ix=None,
) -> tuple:
    """Write one prefill slice `chunk` [B, C] (columns [pos-prefix, ...)
    of the row's LEFT-padded suffix) into the page tables at slots
    [pos, pos + C). Non-final slices only fill KV (the lm_head matmul is
    skipped via return_features); the final slice samples the first new
    token per row at generation index 0 — byte-identical to one-shot
    `paged_prefill` because the last chunk's last position IS the same
    query the one-shot program sampled from. Returns cache' (non-final)
    or (cache', first_tokens [B]) (final)."""
    kwargs = dict(
        train=False,
        decode=True,
        mutable=["cache"],
        pad=pad,
        pages=pages,
        pos=jnp.asarray(pos, jnp.int32),
        kv_layout=kv_layout,
        prefix_lens=jnp.asarray(prefix_lens, jnp.int32),
        **_adapter_kw(adapter_ix),
    )
    if not final:
        _, vars1 = module.apply(
            {"params": params, "cache": cache},
            chunk.astype(jnp.int32),
            return_features=True,  # KV writes only — skip the vocab matmul
            **kwargs,
        )
        return vars1["cache"]
    logits, vars1 = module.apply(
        {"params": params, "cache": cache}, chunk.astype(jnp.int32), **kwargs
    )
    row_keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray(seeds, jnp.int32))
    first = _sample_rows(
        logits[:, -1].astype(jnp.float32),
        _row_rngs(row_keys, 0),
        temperature,
        top_k,
    )
    return vars1["cache"], first


def jit_paged_prefill_chunk(
    module,
    *,
    kv_layout: PagedKVLayout,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    final: bool = False,
):
    """Compiled prefill slice: (params, cache, chunk, pad, prefix_lens,
    pages, seeds, pos) → cache' (non-final) or (cache', first) (final).
    Cache DONATED; pos is a traced scalar and prefix_lens a traced [B]
    vector, so every slice of every row — whatever its cached-prefix
    width — reuses one compile per (B, C, n_pages) shape."""

    def prefill_slice(params, cache, chunk, pad, prefix_lens, pages, seeds,
                      pos, adapter_ix=None):
        return paged_prefill_chunk(
            module, params, cache, chunk,
            pad=pad, pages=pages, kv_layout=kv_layout,
            prefix_lens=prefix_lens, pos=pos,
            temperature=temperature, top_k=top_k, seeds=seeds, final=final,
            adapter_ix=adapter_ix,
        )

    if final:  # a program of its own (it samples): its own name in a trace
        prefill_slice.__name__ = prefill_slice.__qualname__ = "prefill_slice_final"
    return jax.jit(prefill_slice, donate_argnums=(1,))


def paged_step(
    module,
    params,
    cache,
    tok,
    done,
    *,
    pad,
    prefix_lens,
    pages,
    kv_layout: PagedKVLayout,
    pos,
    g,
    seeds,
    temperature: float,
    top_k: Optional[int],
    eos_id: Optional[int],
    adapter_ix=None,
) -> tuple:
    """ONE decode step for a continuous batch: feed `tok` [B] at per-row
    frontiers `pos` [B] and sample each row's next token at its own
    generation index `g` [B]. Identical math to one iteration of
    `paged_decode_chunk`'s scan body — same fold_in(key, g) streams,
    same eos latch — just with pos/g/prefix as per-row runtime vectors
    so rows of different ages and prefix widths share the dispatch.
    Returns (cache', nxt [B], done' [B])."""
    logits, out_vars = module.apply(
        {"params": params, "cache": cache},
        jnp.asarray(tok, jnp.int32)[:, None],
        train=False,
        decode=True,
        mutable=["cache"],
        pad=pad,
        pages=pages,
        pos=jnp.asarray(pos, jnp.int32),
        kv_layout=kv_layout,
        prefix_lens=jnp.asarray(prefix_lens, jnp.int32),
        **_adapter_kw(adapter_ix),
    )
    row_keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray(seeds, jnp.int32))
    rngs = jax.vmap(jax.random.fold_in)(row_keys, jnp.asarray(g, jnp.int32))
    nxt = _sample_rows(
        logits[:, -1].astype(jnp.float32), rngs, temperature, top_k
    )
    if eos_id is not None:
        done = done | (jnp.asarray(tok, jnp.int32) == eos_id)
        nxt = jnp.where(done, eos_id, nxt)
    return out_vars["cache"], nxt, done


def jit_paged_step(
    module,
    *,
    kv_layout: PagedKVLayout,
    temperature: float,
    top_k: Optional[int],
    eos_id: Optional[int],
):
    """Compiled continuous-batching decode step: (params, cache, tok,
    done, pad, prefix_lens, pages, seeds, pos, g) → (cache', nxt,
    done'). Cache DONATED; every traced argument is per-row, so one
    compile per (B, n_pages, sampling) signature serves the whole mixed
    step stream."""

    def decode_step(params, cache, tok, done, pad, prefix_lens, pages, seeds,
                    pos, g, adapter_ix=None):
        return paged_step(
            module, params, cache, tok, done,
            pad=pad, prefix_lens=prefix_lens, pages=pages,
            kv_layout=kv_layout, pos=pos, g=g, seeds=seeds,
            temperature=temperature, top_k=top_k, eos_id=eos_id,
            adapter_ix=adapter_ix,
        )

    return jax.jit(decode_step, donate_argnums=(1,))


def beam_search(
    module,
    params,
    prompt: jnp.ndarray,
    *,
    max_new_tokens: int,
    num_beams: int = 4,
    length_penalty: float = 1.0,
    eos_id: Optional[int] = None,
) -> jnp.ndarray:
    """Beam-search decode: returns the best sequence per batch row,
    [B, P + max_new_tokens].

    Same compiled-shape discipline as generate(): one prefill on the
    prompt (computed once per batch row, then tiled to beams), then a
    static-length scan where each step expands every beam over the vocab,
    keeps the top `num_beams` continuations, and reorders the KV cache by
    each survivor's parent beam (a batch-dim gather on the cache pytree).

    Scoring follows the canonical (HF-style) recipe. Without `eos_id`,
    mid-scan pruning ranks beams by RAW accumulated log-prob and
    `length_penalty` applies only to the FINAL ranking (dividing by
    length**length_penalty; >1 favors longer). With `eos_id`, each step
    expands the top 2*nb candidates: those ending in eos move into a
    FINISHED-HYPOTHESIS buffer (ranked by length-penalized score, worst
    evicted), the best nb non-eos candidates stay live — so a short
    finished hypothesis the final ranking would prefer can never be
    evicted by a live beam's raw score. The final answer is the best of
    {finished buffer, live beams} under the length penalty."""
    cfg = module.cfg
    B, P = prompt.shape
    total = P + int(max_new_tokens)
    if total > cfg.seq_len:
        raise ValueError(
            f"prompt ({P}) + max_new_tokens ({max_new_tokens}) = {total} "
            f"exceeds the model's seq_len {cfg.seq_len} (the KV cache size)"
        )
    nb = int(num_beams)
    if nb < 1:
        raise ValueError("num_beams must be >= 1")
    if nb > cfg.vocab_size:
        raise ValueError(
            f"num_beams ({nb}) cannot exceed vocab_size ({cfg.vocab_size})"
        )
    prompt = prompt.astype(jnp.int32)
    BN = B * nb

    def tile(x):  # [B, ...] -> [B*nb, ...] (beam-major per batch row)
        return jnp.repeat(x, nb, axis=0)

    # cache creation + prefill ONCE per batch row ([B, P] — all nb beams
    # of a row share the prefix state), then tile the cache to beams;
    # prefilling the tiled batch would cost nb x the FLOPs for identical
    # outputs
    _, init_vars = module.apply(
        {"params": params},
        jnp.zeros((B, 1), jnp.int32),
        train=False,
        decode=True,
        mutable=["cache"],
    )
    logits, vars1 = module.apply(
        {"params": params, "cache": init_vars["cache"]},
        prompt,
        train=False,
        decode=True,
        mutable=["cache"],
    )
    # cache batch axis: 0 in the per-layer module layout, 1 under
    # nn.scan-over-layers (leaves gain a leading [n_layers] dim). K/V
    # leaves have ndim >= 3; cache_index ((), or [n_layers] under scan)
    # is beam-invariant and is never tiled or gathered.
    cache_batch_axis = 1 if getattr(cfg, "scan_layers", False) else 0

    def beam_cache_map(fn, tree):
        return jax.tree.map(
            lambda c: fn(c) if hasattr(c, "ndim") and c.ndim >= 3 else c,
            tree,
        )

    cache0 = beam_cache_map(
        lambda c: jnp.repeat(c, nb, axis=cache_batch_axis), vars1["cache"]
    )
    first_logp = jax.nn.log_softmax(
        logits[:, -1].astype(jnp.float32), axis=-1
    )  # [B, V]
    V = first_logp.shape[-1]
    lp = float(length_penalty)
    if eos_id is None:
        # first expansion: row's beams take the top-nb distinct first tokens
        scores0, tok0 = jax.lax.top_k(first_logp, nb)  # [B, nb]
    else:
        # expand 2*nb so that after eos candidates leave for the finished
        # buffer at least nb live continuations remain (eos appears at most
        # once per parent, so <= nb of the 2*nb candidates are eos)
        k0 = min(2 * nb, V)
        sc2, tok2 = jax.lax.top_k(first_logp, k0)  # [B, k0]
        is_eos0 = tok2 == eos_id
        scores0, pick0 = jax.lax.top_k(
            jnp.where(is_eos0, -jnp.inf, sc2), nb
        )
        tok0 = jnp.take_along_axis(tok2, pick0, axis=1)  # [B, nb] live
        # finished buffer: [B, nb] penalized scores + full sequences; the
        # first-step eos hypotheses have generated length 1
        fin_scores = jax.lax.top_k(
            jnp.where(is_eos0, sc2, -jnp.inf), min(nb, k0)
        )[0]
        if fin_scores.shape[1] < nb:  # pad (top_k k0 < nb can't happen; safety)
            fin_scores = jnp.pad(
                fin_scores, ((0, 0), (0, nb - fin_scores.shape[1])),
                constant_values=-jnp.inf,
            )
        fin_buf = jnp.zeros((B, nb, total), jnp.int32)
        fin_buf = fin_buf.at[:, :, :P].set(prompt[:, None, :])
        fin_buf = fin_buf.at[:, :, P].set(eos_id)

    buf = jnp.zeros((BN, total), jnp.int32)
    buf = jax.lax.dynamic_update_slice(buf, tile(prompt), (0, 0))
    buf = buf.at[:, P].set(tok0.reshape(BN))

    def gather_rows(x, flat, axis):
        return jnp.take(x, flat, axis=axis)

    def gather_beams_cache(tree, parent):  # parent: [B, nb]
        flat = (jnp.arange(B)[:, None] * nb + parent).reshape(BN)
        return beam_cache_map(
            lambda c: gather_rows(c, flat, cache_batch_axis), tree
        )

    def expand(cache, buf, scores, t):
        """Shared per-step expansion: feed position t, return candidate
        log-probs and the updated cache."""
        tok = jax.lax.dynamic_slice(buf, (0, t), (BN, 1))
        logits, out_vars = module.apply(
            {"params": params, "cache": cache},
            tok,
            train=False,
            decode=True,
            mutable=["cache"],
        )
        logp = jax.nn.log_softmax(
            logits[:, -1].astype(jnp.float32), axis=-1
        ).reshape(B, nb, V)
        return scores[:, :, None] + logp, out_vars["cache"]  # [B, nb, V]

    def keep_live(cache, buf, parent, nxt, t):
        flat = (jnp.arange(B)[:, None] * nb + parent).reshape(BN)
        cache = gather_beams_cache(cache, parent)
        buf = buf[flat]
        return cache, jax.lax.dynamic_update_slice(
            buf, nxt.reshape(BN, 1), (0, t + 1)
        )

    def step_raw(carry, t):
        """No eos: canonical raw-score pruning over nb*V candidates."""
        cache, buf, scores = carry
        cand, cache = expand(cache, buf, scores, t)
        scores, idx = jax.lax.top_k(cand.reshape(B, nb * V), nb)
        parent, nxt = idx // V, (idx % V).astype(jnp.int32)  # [B, nb]
        cache, buf = keep_live(cache, buf, parent, nxt, t)
        return (cache, buf, scores), None

    def step_eos(carry, t):
        """With eos: top 2*nb candidates; eos continuations move into the
        finished buffer (length-penalized, worst evicted), the best nb
        non-eos candidates stay live."""
        cache, buf, scores, fin_scores, fin_buf = carry
        cand, cache = expand(cache, buf, scores, t)
        k = min(2 * nb, nb * V)
        cand_sc, idx = jax.lax.top_k(cand.reshape(B, nb * V), k)  # [B, k]
        parent, nxt = idx // V, (idx % V).astype(jnp.int32)
        is_eos = nxt == eos_id

        # candidate sequences [B, k, total]: parent's buffer + new token
        parent_buf = jnp.take_along_axis(
            buf.reshape(B, nb, total), parent[:, :, None], axis=1
        )
        cand_buf = jax.lax.dynamic_update_slice_in_dim(
            parent_buf, nxt[:, :, None], t + 1, axis=2
        )
        # finished insertion: generated length includes this eos token
        gen_len = (t + 2 - P).astype(jnp.float32)
        pen = jnp.where(is_eos, cand_sc / gen_len**lp, -jnp.inf)
        all_sc = jnp.concatenate([fin_scores, pen], axis=1)  # [B, nb+k]
        all_buf = jnp.concatenate([fin_buf, cand_buf], axis=1)
        fin_scores, fidx = jax.lax.top_k(all_sc, nb)
        fin_buf = jnp.take_along_axis(all_buf, fidx[:, :, None], axis=1)

        # live continuation: best nb non-eos candidates
        scores, pick = jax.lax.top_k(
            jnp.where(is_eos, -jnp.inf, cand_sc), nb
        )
        parent = jnp.take_along_axis(parent, pick, axis=1)
        nxt = jnp.take_along_axis(nxt, pick, axis=1)
        cache, buf = keep_live(cache, buf, parent, nxt, t)
        return (cache, buf, scores, fin_scores, fin_buf), None

    if eos_id is None:
        carry = (cache0, buf, scores0)
        if max_new_tokens > 1:
            carry, _ = jax.lax.scan(
                step_raw, carry, jnp.arange(P, total - 1)
            )
        _, buf, scores = carry
        out = buf.reshape(B, nb, total)
        final = scores / (float(max_new_tokens) ** lp)
        best = jnp.argmax(final, axis=1)
        return jnp.take_along_axis(out, best[:, None, None], axis=1)[:, 0]

    carry = (cache0, buf, scores0, fin_scores, fin_buf)
    if max_new_tokens > 1:
        carry, _ = jax.lax.scan(step_eos, carry, jnp.arange(P, total - 1))
    _, buf, scores, fin_scores, fin_buf = carry
    # final ranking: live beams (never eos-ended → full length) against the
    # finished buffer (already length-penalized)
    live_pen = scores / (float(max_new_tokens) ** lp)
    all_sc = jnp.concatenate([live_pen, fin_scores], axis=1)  # [B, 2nb]
    all_buf = jnp.concatenate(
        [buf.reshape(B, nb, total), fin_buf], axis=1
    )
    best = jnp.argmax(all_sc, axis=1)
    sel = jnp.take_along_axis(all_buf, best[:, None, None], axis=1)[:, 0]
    # finished buffers carry stale parent tokens after their eos — pad with
    # eos like generate() does so callers can truncate uniformly
    gen = sel[:, P:]
    seen = jnp.cumsum(gen == eos_id, axis=1) > 0
    after = jnp.concatenate(
        [jnp.zeros((B, 1), bool), seen[:, :-1]], axis=1
    )
    return sel.at[:, P:].set(jnp.where(after, eos_id, gen))
