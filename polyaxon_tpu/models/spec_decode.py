"""Self-speculative decoding: n-gram drafts + one batched verify pass.

Plain decode pays one full-model forward per token. This module keeps the
model's outputs BYTE-IDENTICAL while amortizing that forward over several
tokens at once:

  1. DRAFT (host, free): a per-row n-gram index over the row's own
     prompt + committed output proposes K likely continuations — no
     second model to place, and repetitive spans (code, templates,
     shared-prefix boilerplate) hit long runs. A real draft MODEL
     (models/draft.ModelDrafter) can stand in for the n-gram index via
     `spec_generate(drafter=...)` — stronger proposals on novel text,
     same verify/commit machinery, still byte-identical.
  2. VERIFY (device, one forward): the previously sampled token plus the
     K drafts run through the decode path as ONE [B, K+1] window. Slot
     semantics are unchanged — position i writes cache slot pos + i and
     attends slots <= pos + i — so the window's logits at index i equal
     exactly what plain decode would have produced after feeding the
     same i tokens. From those logits the window re-derives the BASELINE
     sample for every generation index (per-row `fold_in(key, g)` — the
     same stream `generate()`/`paged_decode_chunk` use), giving targets
     t_0..t_K.
  3. ACCEPT (host): the longest prefix where draft == target commits
     (plus target_{accept} itself, the "bonus" token — it came from
     logits whose context is fully committed). By induction every
     committed token is precisely the token the non-speculative sampler
     would have emitted: acceptance is exact-match against the baseline
     stream, not a probabilistic rejection bound.

Rollback is free: a rejected draft's K/V sits in slots
[pos + ncommit, pos + K], all of which the NEXT window rewrites before
any query can attend them (its write range [pos', pos' + K],
pos' = pos + ncommit, covers the stale range), and the live mask
(slot <= pos + i) keeps them dead meanwhile. On the paged path writes
never leave the row's own table (shared COW prefix pages sit below pos
and stay read-only; overflow past the table drops via the fill/drop
scatter in transformer.Attention).

Rows of one coalesced group accept different lengths, so `pos` and
`start_g` are per-row [B] vectors (transformer.Attention's per-row
branch). Because the sample stream keys on GENERATION index only, a
row's tokens are invariant to its neighbors' accept lengths — the same
order-invariance that already makes coalescing seed-safe.

Restriction: sampled (temperature > 0) speculation needs PER-ROW seeds.
The scalar-seed stream folds the key by absolute buffer position and
draws one categorical over the whole batch — it cannot be replayed once
rows sit at different frontiers — so `spec_generate` rejects it rather
than silently changing outputs. Greedy decode needs no keys at all.

No wall clocks in here: speculation orders everything by logical
generation index (scripts/lint_telemetry.py pins this module clock-free
alongside models/quant.py).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .generate import _sample_rows
from .kv_pages import PagedKVLayout


# ------------------------------------------------------------------ draft side
class NgramDrafter:
    """Per-row suffix→continuation index over the row's own token history.

    `index[(t_{i-n+1}..t_i)] = i` maps each n-gram (n = ngram_max..1,
    longest match wins) to the LATEST position it occurred with a
    continuation, so `propose` replays what followed last time. Misses
    fall back to repeating the last token — on truly novel text the
    drafts just get rejected (costing nothing but the already-batched
    verify width), while repetitive spans draft whole runs correctly.
    """

    def __init__(self, tokens, *, ngram_max: int = 3):
        self.ns = tuple(range(int(ngram_max), 0, -1))
        self.tokens: list[int] = []
        self.index: dict[tuple, int] = {}
        self.extend(tokens)

    def extend(self, tokens) -> None:
        for t in tokens:
            self.tokens.append(int(t))
            i = len(self.tokens) - 2  # newest position that has a continuation
            if i < 0:
                continue
            for n in self.ns:
                if i + 1 >= n:
                    self.index[tuple(self.tokens[i + 1 - n : i + 1])] = i

    def propose(self, k: int) -> list[int]:
        if not self.tokens:
            return [0] * k
        for n in self.ns:
            if len(self.tokens) < n:
                continue
            j = self.index.get(tuple(self.tokens[-n:]))
            if j is None:
                continue
            cont = self.tokens[j + 1 : j + 1 + k]
            if cont:
                return (cont + [cont[-1]] * k)[:k]
        return [self.tokens[-1]] * k


# ----------------------------------------------------------------- verify side
def _verify_targets(
    logits,
    fed,
    row_keys,
    start_g,
    done,
    *,
    temperature: float,
    top_k: Optional[int],
    eos_id: Optional[int],
):
    """Baseline targets + accept lengths from one verify window.

    logits: [B, S, V] from feeding `fed` [B, S] (fed[:, 0] = last
    committed token, fed[:, 1:] = drafts); start_g: [B] generation index
    of the window's FIRST sample; done: [B] eos latch entering the
    window. Returns (targets [B, S], accept [B]) where targets[:, i] is
    the baseline sample at generation index start_g + i (eos-pinned via
    the same fed-token latch generate() uses) and accept counts the
    leading drafts that match their target.
    """
    B, S = fed.shape
    lg = jnp.moveaxis(logits.astype(jnp.float32), 1, 0)  # [S, B, V]

    def step(carry, xs):
        done = carry
        lgt, f, i = xs
        if eos_id is not None:
            done = done | (f == eos_id)
        keys = jax.vmap(jax.random.fold_in)(row_keys, start_g + i)
        t = _sample_rows(lgt, keys, temperature, top_k)
        if eos_id is not None:
            t = jnp.where(done, eos_id, t)
        return done, t

    _, targets = jax.lax.scan(step, done, (lg, fed.T, jnp.arange(S)))
    targets = targets.T  # [B, S]
    match = (fed[:, 1:] == targets[:, :-1]).astype(jnp.int32)
    accept = jnp.cumprod(match, axis=1).sum(axis=1)
    return targets, accept


def jit_spec_prefill(module, *, temperature: float, top_k: Optional[int]):
    """Compiled dense prefill for the speculative path: (params, prompt,
    pad, seeds) → (cache, first [B]). Identical math to generate()'s
    prefill — creation apply, one batched prompt forward, generation
    index 0 sampled from the last-position logits."""
    from .generate import _adapter_kw, _row_rngs

    def spec_prefill(params, prompt, pad, seeds, adapter_ix=None):
        B = prompt.shape[0]
        _, init_vars = module.apply(
            {"params": params},
            jnp.zeros((B, 1), jnp.int32),
            train=False,
            decode=True,
            mutable=["cache"],
        )
        logits, vars1 = module.apply(
            {"params": params, "cache": init_vars["cache"]},
            prompt.astype(jnp.int32),
            train=False,
            decode=True,
            mutable=["cache"],
            pad=pad,
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

    return jax.jit(spec_prefill)


def jit_spec_verify(
    module,
    *,
    temperature: float,
    top_k: Optional[int],
    eos_id: Optional[int],
):
    """Compiled dense verify window: (params, cache, fed [B, K+1], done,
    pad, seeds, pos [B], start_g [B]) → (cache', targets [B, K+1],
    accept [B]). Cache is DONATED; pos/start_g are traced per-row
    vectors, so every window of every group reuses one compile per
    (batch, K+1) shape."""

    def spec_verify(params, cache, fed, done, pad, seeds, pos, start_g,
                    adapter_ix=None):
        from .generate import _adapter_kw

        row_keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray(seeds, jnp.int32))
        logits, vars1 = module.apply(
            {"params": params, "cache": cache},
            fed.astype(jnp.int32),
            train=False,
            decode=True,
            mutable=["cache"],
            pad=pad,
            pos=jnp.asarray(pos, jnp.int32),
            **_adapter_kw(adapter_ix),
        )
        targets, accept = _verify_targets(
            logits, fed, row_keys, jnp.asarray(start_g, jnp.int32), done,
            temperature=temperature, top_k=top_k, eos_id=eos_id,
        )
        return vars1["cache"], targets, accept

    return jax.jit(spec_verify, donate_argnums=(1,))


def jit_spec_verify_paged(
    module,
    *,
    kv_layout: PagedKVLayout,
    prefix_len: int,
    temperature: float,
    top_k: Optional[int],
    eos_id: Optional[int],
):
    """Compiled paged verify window — jit_paged_chunk's draft-window
    sibling: (params, cache, fed [B, K+1], done, pad, pages, seeds,
    pos [B], start_g [B]) → (cache', targets, accept). The pool is
    DONATED and written in place through the page tables; writes past a
    row's table span (rejected-tail overflow) drop in the scatter."""

    def spec_verify_paged(params, cache, fed, done, pad, pages, seeds, pos,
                          start_g, adapter_ix=None):
        from .generate import _adapter_kw

        row_keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray(seeds, jnp.int32))
        logits, vars1 = module.apply(
            {"params": params, "cache": cache},
            fed.astype(jnp.int32),
            train=False,
            decode=True,
            mutable=["cache"],
            pad=pad,
            pages=pages,
            pos=jnp.asarray(pos, jnp.int32),
            kv_layout=kv_layout,
            prefix_len=prefix_len,
            **_adapter_kw(adapter_ix),
        )
        targets, accept = _verify_targets(
            logits, fed, row_keys, jnp.asarray(start_g, jnp.int32), done,
            temperature=temperature, top_k=top_k, eos_id=eos_id,
        )
        return vars1["cache"], targets, accept

    return jax.jit(spec_verify_paged, donate_argnums=(1,))


# ------------------------------------------------------------------- host side
def commit_window(fed, targets, accept, remaining, done, eos_id):
    """Host-side accept/commit for one verify window (shared by
    spec_generate and the serving group loops).

    All numpy: fed [B, K+1], targets [B, K+1], accept [B],
    remaining [B] (tokens the row may still emit; <= 0 = inactive row),
    done [B] (baseline eos latch entering the window). Returns
    (committed per-row list, done', remaining', eos_hit [B],
    stats {proposed, accepted, accepted_judged, truncated, rollback}).

    Active rows commit ncommit = min(accept + 1, remaining) tokens —
    always >= 1, so the loop makes progress even at zero acceptance.
    `accepted` counts COMMITTED drafts (ncommit - 1): near
    maxNewTokens the `remaining` clamp can truncate a long accepted run,
    deflating accepted/proposed below the drafter's true quality.
    `accepted_judged` counts every draft the verify forward actually
    matched, truncated or not — the adaptive-K controller consumes this
    corrected figure (a K decision is about the NEXT window, where no
    budget clamp applies), while `truncated` (= judged - committed)
    exposes the gap on /statsz. The two rates diverge only when a row's
    accept run crosses its remaining budget.
    done' replays generate()'s latch (a row latches when a GENERATED eos
    token is FED, i.e. appears among fed[:ncommit]); eos_hit flags rows
    whose committed tokens contain eos — everything after is pinned to
    eos, so the caller can fill and retire the row host-side.
    """
    fed = np.asarray(fed)
    targets = np.asarray(targets)
    accept = np.asarray(accept)
    B, S = fed.shape
    K = S - 1
    done = np.array(done, bool)
    remaining = np.array(remaining, np.int64)
    eos_hit = np.zeros(B, bool)
    committed: list[np.ndarray] = []
    proposed = accepted = judged = truncated = rollback = 0
    for b in range(B):
        if remaining[b] <= 0:
            committed.append(np.empty((0,), np.int32))
            continue
        proposed += K
        n = int(min(int(accept[b]) + 1, remaining[b]))
        toks = targets[b, :n].astype(np.int32)
        committed.append(toks)
        accepted += n - 1
        j = int(min(int(accept[b]), K))
        judged += j
        truncated += j - (n - 1)
        rollback += K - (n - 1)
        if eos_id is not None:
            if (fed[b, :n] == eos_id).any():
                done[b] = True
            if (toks == eos_id).any():
                eos_hit[b] = True
        remaining[b] -= n
    stats = {
        "proposed": proposed,
        "accepted": accepted,
        "accepted_judged": judged,
        "truncated": truncated,
        "rollback": rollback,
    }
    return committed, done, remaining, eos_hit, stats


def spec_generate(
    module,
    params,
    prompt: jnp.ndarray,
    *,
    max_new_tokens: int,
    draft_tokens: int = 4,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    eos_id: Optional[int] = None,
    seeds=None,  # [B] per-row seeds; required when temperature > 0
    prompt_lengths=None,  # [B] true lengths of a LEFT-padded prompt batch
    ngram_max: int = 3,
    prefill_fn=None,  # prebuilt jit_spec_prefill (callers reusing compiles)
    verify_fn=None,  # prebuilt jit_spec_verify
    stats: Optional[dict] = None,  # accumulates proposed/accepted/rollback
    drafter=None,  # models.draft.ModelDrafter — replaces the n-gram index
    controller=None,  # adaptive-K hook: window_k()/observe()/tick_plain()
    adapter_ix=None,  # [B] per-row adapter slot (ISSUE 19); None = slot 0
) -> jnp.ndarray:
    """Speculative drop-in for generate() on the dense cache: same
    [B, P + max_new_tokens] result, byte-identical per row, usually far
    fewer forward passes. See the module docstring for the contract.

    With `controller` (serving.adaptive.AdaptiveSpecController or any
    duck-type) each window asks `window_k()` for its draft width:
    `draft_tokens` becomes the cap, a smaller k shrinks the window, and
    k == 0 degenerates to a width-1 window — EXACTLY one plain decode
    step through the same verify program family, which is the auto-
    disable fallback. After each window the controller is fed the
    truncation-corrected accept counts (`observe`) or, for plain
    windows, a logical re-probe tick (`tick_plain`). jit retraces per
    window width, so an adapting K grows the compile ladder one entry
    per distinct width — bounded by draft_tokens."""
    cfg = module.cfg
    B, P = prompt.shape
    K = int(draft_tokens)
    if K < 1:
        raise ValueError("draft_tokens must be >= 1")
    total = P + int(max_new_tokens)
    if total > cfg.seq_len:
        raise ValueError(
            f"prompt ({P}) + max_new_tokens ({max_new_tokens}) = {total} "
            f"exceeds the model's seq_len {cfg.seq_len} (the KV cache size)"
        )
    if seeds is None:
        if temperature > 0.0:
            raise ValueError(
                "speculative sampling needs per-row seeds: the scalar-seed "
                "stream keys on absolute position and draws one batch-wide "
                "categorical, which cannot be replayed once rows accept "
                "different lengths — pass seeds=[B] (generate() accepts "
                "the same) or use temperature=0"
            )
        seeds = np.zeros(B, np.int32)  # greedy: keys computed but unused
    seeds = jnp.asarray(seeds, jnp.int32)
    if seeds.shape != (B,):
        raise ValueError(f"seeds must be [B]={B}, got {seeds.shape}")

    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt_lengths is None:
        lengths = np.full(B, P, np.int64)
    else:
        lengths = np.asarray(prompt_lengths, np.int64)
    pad = jnp.asarray(P - lengths, jnp.int32)

    if prefill_fn is None:
        prefill_fn = jit_spec_prefill(
            module, temperature=temperature, top_k=top_k
        )
    if verify_fn is None:
        verify_fn = jit_spec_verify(
            module, temperature=temperature, top_k=top_k, eos_id=eos_id
        )

    if adapter_ix is not None:
        adapter_ix = jnp.asarray(adapter_ix, jnp.int32)
    cache, first = (
        prefill_fn(params, prompt, pad, seeds)
        if adapter_ix is None
        else prefill_fn(params, prompt, pad, seeds, adapter_ix)
    )
    first = np.asarray(first)
    prompt_np = np.asarray(prompt)

    buf = np.zeros((B, total), np.int32)
    buf[:, :P] = prompt_np
    buf[:, P] = first

    drafters: list[NgramDrafter] = []
    if drafter is None:
        drafters = [
            NgramDrafter(prompt_np[b, P - lengths[b] :], ngram_max=ngram_max)
            for b in range(B)
        ]
        for b in range(B):
            drafters[b].extend([first[b]])

    tok = first.copy()  # last committed (not yet fed) token per row
    pos = np.full(B, P, np.int64)  # cache slot `tok` will occupy
    start_g = np.ones(B, np.int64)  # generation index of the next sample
    done = np.zeros(B, bool)
    remaining = np.full(B, int(max_new_tokens) - 1, np.int64)
    if eos_id is not None:
        hit = first == eos_id
        buf[hit, P + 1 :] = eos_id  # baseline pins everything after eos
        remaining[hit] = 0

    while (remaining > 0).any():
        k_eff = K if controller is None else min(K, int(controller.window_k()))
        fed = np.empty((B, k_eff + 1), np.int32)
        fed[:, 0] = tok
        if k_eff:
            if drafter is not None:
                fed[:, 1:] = drafter.propose(tok, start_g, k_eff)
                for b in range(B):
                    if remaining[b] <= 0:
                        fed[b, 1:] = tok[b]
            else:
                for b in range(B):
                    fed[b, 1:] = (
                        drafters[b].propose(k_eff)
                        if remaining[b] > 0
                        else tok[b]
                    )
        verify_args = (
            params, cache, jnp.asarray(fed), jnp.asarray(done), pad,
            seeds, jnp.asarray(pos, jnp.int32),
            jnp.asarray(start_g, jnp.int32),
        )
        if adapter_ix is not None:
            verify_args = verify_args + (adapter_ix,)
        cache, targets, accept = verify_fn(*verify_args)
        committed, done, remaining, eos_hit, delta = commit_window(
            fed, targets, accept, remaining, done, eos_id
        )
        if controller is not None:
            if k_eff:
                controller.observe(delta["proposed"], delta["accepted_judged"])
            else:
                controller.tick_plain(1)
        if stats is not None:
            for k, v in delta.items():
                stats[k] = stats.get(k, 0) + v
            stats["windows"] = stats.get("windows", 0) + 1
        for b in range(B):
            toks = committed[b]
            if not len(toks):
                continue
            at = P + start_g[b]
            buf[b, at : at + len(toks)] = toks
            if drafter is None:
                drafters[b].extend(toks)
            tok[b] = toks[-1]
            pos[b] += len(toks)
            start_g[b] += len(toks)
            if eos_hit[b]:
                buf[b, P + start_g[b] :] = eos_id
                remaining[b] = 0
    return jnp.asarray(buf)
