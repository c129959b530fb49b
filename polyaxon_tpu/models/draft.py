"""Draft-model speculative decoding: a real small LM as the proposer.

The n-gram drafter (models/spec_decode.NgramDrafter) is free but blind —
on high-entropy text its accept rate collapses to ~0 and every verify
window is pure overhead. This module supplies the stronger proposer the
adaptive-speculation stack (ISSUE 15) ramps K against: a SMALL draft
model with the same architecture and tokenizer as the target — fewer
layers/dims via the `draft:` sub-config on the model config — that runs
K cheap autoregressive steps through its OWN dense cache and hands the
proposals to the existing one-batched-verify + commit_window path
unchanged.

Byte-identity is structural, not assumed: acceptance is exact-match
against the target's baseline sample stream, so the draft model can
NEVER change output bytes — only the accept rate. That makes the split
clean: the drafter samples with the SAME per-row `fold_in(key, g)`
schedule as the target (maximizing sampled-mode agreement when draft ≈
target), but a randomly initialized draft is merely slow, never wrong.

Cache discipline — why no correction pass exists: each `propose` feeds
[tok, d_1 .. d_{K-1}] into the draft cache at slots
[pos, .., pos + K - 1]. If the verify commits n tokens, the first n - 1
drafts matched their targets, so draft slots [pos, pos + n - 1] already
hold exactly the committed tokens' K/V; the stale tail is overwritten by
the next window's writes (which start at pos + n) before any query can
attend it — the same free-rollback argument as the target cache
(spec_decode module docstring). The drafter therefore keeps no host
mirror of the token stream at all: its cache position is a pure function
of the generation index (`pos = prompt_width + start_g - 1`).

The draft cache is deliberately its own DENSE left-padded layout —
decoupled from the target's paged/prefix geometry. On paged groups the
drafter re-prefills the (bucketed) prompt itself: the draft is a
fraction of the target's cost, and independence is what lets one drafter
implementation serve dense spec, paged spec and step-engine lanes alike.

No wall clocks in here: drafting orders everything by logical generation
index (scripts/lint_telemetry.py rule 12 pins this module clock-free
alongside serving/adaptive.py).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .generate import _sample_rows

#: fields the `draft:` sub-config may NOT override — the drafter must
#: share the target's tokenizer and propose over the same vocabulary
_PINNED = ("vocab_size",)


def draft_config(cfg):
    """The small-draft config derived from a base TransformerConfig.

    Applies the `draft:` overrides carried on `cfg.draft` (a normalized
    (key, value) tuple — see transformer._make_config); when no override
    names `n_layers`, the draft defaults to half the target's depth.
    The draft never re-declares a `draft:` of its own."""
    over = dict(cfg.draft) if cfg.draft else {}
    for k in _PINNED:
        if k in over and over[k] != getattr(cfg, k):
            raise ValueError(
                f"draft model must share the tokenizer: {k} may not change"
            )
    over.setdefault("n_layers", max(1, cfg.n_layers // 2))
    over["draft"] = ()
    fields = {f.name for f in dataclasses.fields(type(cfg))}
    unknown = set(over) - fields
    if unknown:
        raise ValueError(f"unknown draft config fields: {sorted(unknown)}")
    return dataclasses.replace(cfg, **over)


def derive_draft_params(params, draft_cfg, *, base_cfg=None):
    """Draft params by LAYER TRUNCATION of the base tree: draft layer i
    takes base layer i; embed, final_norm and lm_head are shared. Only
    valid when the draft keeps the base's widths (dim/heads/ffn) — a
    width-changed draft has no base slice to inherit and must be trained
    or randomly initialized (`init_draft_params`).

    Handles both stacking modes: per-layer `layer_{i}` subtrees and the
    nn.scan layout (`layers/...` leaves with a leading layer axis)."""
    n = draft_cfg.n_layers
    if base_cfg is not None:
        for f in ("dim", "n_heads", "n_kv_heads", "hidden_dim"):
            if getattr(draft_cfg, f) != getattr(base_cfg, f):
                raise ValueError(
                    f"cannot derive draft params by truncation: draft "
                    f"changes {f} (train or randomly init the draft "
                    f"instead)"
                )
        if n > base_cfg.n_layers:
            raise ValueError(
                f"draft n_layers {n} exceeds base {base_cfg.n_layers}"
            )
    out = {}
    for k, v in params.items():
        if k == "layers":  # nn.scan stack: leading layer axis on leaves
            out[k] = jax.tree.map(lambda a: a[:n], v)
        elif k.startswith("layer_"):
            if int(k.split("_", 1)[1]) < n:
                out[k] = v
        else:
            out[k] = v  # embed / final_norm / lm_head shared verbatim
    return out


def init_draft_params(module, seed: int = 0):
    """Random draft weights: accept rate will be ~0, output bytes are
    unaffected (acceptance is exact-match) — the fallback when the draft
    changes widths and no trained draft checkpoint exists."""
    return module.init(
        {"params": jax.random.PRNGKey(seed)},
        jnp.zeros((1, 8), jnp.int32),
        train=False,
    )["params"]


def build_draft(module, params, *, overrides=None):
    """(draft_module, draft_params, derived) for a base transformer.

    `overrides` (dict or (key, value) tuple) layers over the config's
    own `draft:` sub-config. Params derive by layer truncation when the
    draft keeps the base widths; otherwise they fall back to random init
    and `derived` is False so callers can surface the accept-rate cost."""
    cfg = module.cfg
    if overrides:
        if hasattr(overrides, "items"):
            overrides = tuple(sorted(
                (str(k), tuple(v) if isinstance(v, list) else v)
                for k, v in overrides.items()
            ))
        cfg = dataclasses.replace(cfg, draft=tuple(overrides))
    dcfg = draft_config(cfg)
    dmodule = type(module)(dcfg)
    try:
        dparams = derive_draft_params(params, dcfg, base_cfg=cfg)
        return dmodule, dparams, True
    except ValueError:
        return dmodule, init_draft_params(dmodule), False


# ----------------------------------------------------------------- compiled fns
def jit_draft_prefill(module):
    """Compiled draft prefill: (params, prompt [B, P], pad [B]) → cache.
    One batched forward filling the draft's dense cache; the first
    sampled token comes from the TARGET's prefill, never from here."""

    def draft_prefill(params, prompt, pad):
        B = prompt.shape[0]
        _, init_vars = module.apply(
            {"params": params},
            jnp.zeros((B, 1), jnp.int32),
            train=False,
            decode=True,
            mutable=["cache"],
        )
        _, vars1 = module.apply(
            {"params": params, "cache": init_vars["cache"]},
            prompt.astype(jnp.int32),
            train=False,
            decode=True,
            mutable=["cache"],
            pad=pad,
        )
        return vars1["cache"]

    return jax.jit(draft_prefill)


def jit_draft_propose(module, *, steps: int, temperature: float,
                      top_k: Optional[int]):
    """Compiled K-step draft rollout: (params, cache, tok [B], pad,
    seeds, pos [B], start_g [B]) → (cache', drafts [B, steps]).

    Step i feeds the previous token at slot pos + i and samples the
    draft for generation index start_g + i with the TARGET's own key
    schedule `fold_in(row_key, g)` — when the draft function equals the
    target function, sampled proposals match targets exactly. The cache
    is DONATED; pos/start_g are traced per-row vectors, so every window
    of every group reuses one compile per (batch, steps) shape."""

    def spec_draft(params, cache, tok, pad, seeds, pos, start_g):
        row_keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray(seeds, jnp.int32))
        pos = jnp.asarray(pos, jnp.int32)
        start_g = jnp.asarray(start_g, jnp.int32)

        def step(carry, i):
            cache, tok = carry
            logits, vars1 = module.apply(
                {"params": params, "cache": cache},
                tok[:, None],
                train=False,
                decode=True,
                mutable=["cache"],
                pad=pad,
                pos=pos + i,
            )
            keys = jax.vmap(jax.random.fold_in)(row_keys, start_g + i)
            nxt = _sample_rows(
                logits[:, -1].astype(jnp.float32), keys, temperature, top_k
            )
            return (vars1["cache"], nxt), nxt

        (cache, last), drafts = jax.lax.scan(
            step, (cache, jnp.asarray(tok, jnp.int32)), jnp.arange(steps)
        )
        # the scan fed [tok, d_1 .. d_{steps-1}] into slots
        # [pos, pos + steps - 1]; d_steps was sampled but never fed. On a
        # FULL-accept window the bonus commit advances the frontier past
        # slot pos + steps, whose token is then exactly d_steps — write
        # its K/V now (logits discarded) or the next window attends a
        # hole. On partial accept the slot is stale and dies under the
        # live mask like every rejected tail.
        _, vars1 = module.apply(
            {"params": params, "cache": cache},
            last[:, None],
            train=False,
            decode=True,
            mutable=["cache"],
            pad=pad,
            pos=pos + steps,
        )
        return vars1["cache"], drafts.T  # [B, steps]

    return jax.jit(spec_draft, donate_argnums=(1,))


# ------------------------------------------------------------------ host driver
class ModelDrafter:
    """Batched draft proposer over its own dense left-padded cache.

    Drop-in alternative to the per-row NgramDrafter at the three
    proposal sites (spec_generate, the paged group loop, the step
    engine's spec lanes): construct once per group with the BUCKETED
    prompt batch, then `propose(tok, start_g, k)` each window. The
    drafter derives its cache frontier from the generation index alone
    (`prompt_width + start_g - 1`), so it composes with any target-side
    geometry — dense, paged, prefix-cached or chunk-prefilled — without
    mirroring it.
    """

    def __init__(self, module, params, prompts, lengths, *, seeds,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 prefill_fn=None, propose_fns=None):
        prompts = jnp.asarray(prompts, jnp.int32)
        B, P = prompts.shape
        total_needed = P + 1
        if total_needed > module.cfg.seq_len:
            raise ValueError(
                f"draft seq_len {module.cfg.seq_len} cannot hold the "
                f"prompt bucket {P}"
            )
        self.module = module
        self.params = params
        self.temperature = float(temperature)
        self.top_k = top_k
        self.base = P  # cache slot of generation index 0's token
        self.pad = jnp.asarray(
            P - np.asarray(lengths, np.int64), jnp.int32
        )
        self.seeds = jnp.asarray(seeds, jnp.int32)
        # propose programs memoized per window size; callers that share
        # compiles across groups pass one dict for all drafters
        self._propose_fns = propose_fns if propose_fns is not None else {}
        pf = prefill_fn if prefill_fn is not None else jit_draft_prefill(module)
        self.cache = pf(params, prompts, self.pad)

    def _fn(self, k: int):
        # keyed on the full static signature: callers share one dict
        # across drafters/groups with differing sampling params
        key = (k, self.temperature, self.top_k)
        fn = self._propose_fns.get(key)
        if fn is None:
            fn = jit_draft_propose(
                self.module, steps=k,
                temperature=self.temperature, top_k=self.top_k,
            )
            self._propose_fns[key] = fn
        return fn

    def propose(self, tok, start_g, k: int) -> np.ndarray:
        """Drafts [B, k] for generation indices start_g .. start_g+k-1.
        `tok` [B] is each row's last committed (not yet fed) token;
        `start_g` [B] the generation index its successor will take."""
        if k < 1:
            return np.empty((len(np.atleast_1d(np.asarray(tok))), 0), np.int32)
        start_g = np.asarray(start_g, np.int64)
        pos = self.base + start_g - 1
        self.cache, drafts = self._fn(k)(
            self.params, self.cache, jnp.asarray(tok, jnp.int32), self.pad,
            self.seeds, jnp.asarray(pos, jnp.int32),
            jnp.asarray(start_g, jnp.int32),
        )
        return np.asarray(drafts)
