"""Serving-side owner of the block-paged KV cache (ISSUE 6).

`KVCacheManager` glues the host accounting (models/kv_pages.py: PagePool
refcounts/reservations + content-addressed PrefixCache) to the device
pool pytree (models/generate.make_paged_cache) and the coalescer:

* **Admission** — `plan_row()` runs on the HTTP producer threads: look
  up the longest cached prefix, bucket the remaining suffix, and RESERVE
  the row's worst-case page demand. A reservation that cannot be
  satisfied first tries LRU eviction of idle prefix entries, then sheds
  with `ShedError(reason="kv_pages")` → HTTP 503 via the PR 5 path — the
  pool can never OOM mid-decode because reserved pages are guaranteed
  convertible (PagePool invariant: reserved <= free).
* **Lazy allocation** — `ensure_pages()` converts reservations into
  pages only as decode actually advances (the decode worker calls it
  before prefill and before each chunk), so a request that finishes
  early on eos never touches its tail pages.
* **Prefix harvest** — after a group completes, `harvest()` copies each
  row's page-aligned prompt prefix into freshly allocated pool pages
  (a jitted gather/scatter, cache donated) and indexes every chain link
  in the PrefixCache, so the next request sharing that prefix skips its
  prefill entirely (its rows alias the pages read-only: copy-on-write
  is free because decode only writes slots >= prefix_len).

Page table layout per row (width = pages_for(L + pb + nb - 1)):
`[shared prefix pages | own pages, allocated lazily | scratch]` — the
scratch page backs not-yet-allocated tail entries and every slot of
batch-padding dummy rows; its garbage is masked dead in attention (or
belongs to dummy rows whose output is dropped).

Threading: producer threads plan/release, the single decode worker
allocates/harvests — every pool/index/table mutation happens under one
lock. No wall clocks here (PrefixCache recency is a logical tick); the
telemetry lint pins that.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Optional

from ..chaos.injector import inject
from ..models.kv_pages import (
    PagedKVLayout,
    PagePool,
    PagePoolExhausted,
    PrefixCache,
    PrefixEntry,
    page_hashes,
)
from .batching import ServingError, ShedError
from .spill import SpillManager, SpillPayload


@dataclasses.dataclass
class RowPlan:
    """One admitted row's paging state, attached to its PendingRequest.
    Created (and reserved) at admission, mutated by the decode worker as
    pages materialize, released exactly once when the request finishes."""

    prefix_len: int  # L: tokens served from the prefix cache (page-aligned)
    prefix_pages: tuple  # shared page ids (read-only for this row)
    prefix_entry: Optional[PrefixEntry]
    suffix_bucket: int  # pb: the row's own tokens, left-padded to this
    new_bucket: int  # nb
    n_pages: int  # table width = pages_for(L + pb + nb - 1)
    reserved: int  # pages still reserved, not yet allocated
    own_pages: list = dataclasses.field(default_factory=list)
    released: bool = False

    @property
    def prefix_pages_n(self) -> int:
        return len(self.prefix_pages)


class KVCacheManager:
    """Owns the device page pool and every decision about who may write
    which page. See module docstring for the protocol."""

    def __init__(
        self,
        module,
        params,
        *,
        pool_pages: int,
        page_tokens: int = 128,
        prefix_cache: bool = True,
        hash_fn=None,
        observer: Optional[Callable[..., None]] = None,
        kv_quant: str = "none",
        spill_ram_bytes: Optional[int] = None,
        spill_dir: Optional[str] = None,
        spill_dir_bytes: Optional[int] = None,
    ):
        from ..models.generate import make_paged_cache

        if pool_pages < 2:
            raise ValueError(
                f"kv_pool_pages must be >= 2 (1 scratch + data), got {pool_pages}"
            )
        # kv_quant = "int8" swaps the pool payload to int8 + per-slot f32
        # scales (models/quant.quantize_kv) — same page accounting, ~2-3.5x
        # the rows per HBM byte. Host-side admission/prefix logic is
        # untouched: quantization is per-slot, so content hashes over the
        # committed token stream stay valid and COW prefix pages carry
        # write-order-independent bytes.
        self.layout = PagedKVLayout(
            page_tokens=page_tokens, pool_pages=pool_pages, kv_quant=kv_quant
        )
        self.module = module
        self.pool = PagePool(pool_pages, page_tokens)
        self.prefix: Optional[PrefixCache] = (
            PrefixCache(self.pool, hash_fn=hash_fn) if prefix_cache else None
        )
        self._observer = observer
        self._lock = threading.RLock()
        # device pool pytree: [pool_pages, page_tokens, nkv, hd] leaves
        # (leading [n_layers] under scan_layers), updated IN PLACE by the
        # donated prefill/chunk/harvest programs
        self.cache = make_paged_cache(module, params, self.layout)
        # the scratch page: backs unallocated table entries and dummy rows
        self.scratch = self.pool.alloc(1)[0]
        self._harvest_fns: dict = {}
        # concurrency accounting: how many rows hold reservations at once —
        # the occupancy win over dense worst-case reservation (acceptance)
        self.active_rows = 0
        self.active_rows_hwm = 0
        self.harvest_skipped = 0
        # ---- tiered prefix spill (ISSUE 17) -------------------------------
        # Evicted prefix entries demote to host RAM / disk instead of
        # vanishing; a later hit restores their pages into the pool. The
        # host MIRROR holds each cached page's bytes keyed by the chain
        # hash at that position (hash h_j commits to pages 0..j, so it
        # uniquely names page j's content); `_mirror_refs[h]` counts live
        # entries whose chain covers position h — bytes drop when the last
        # covering entry evicts (and its spill payload has been built).
        spill_on = bool(
            (spill_ram_bytes or spill_dir) and self.prefix is not None
        )
        self._spill: Optional[SpillManager] = (
            SpillManager(
                ram_bytes=spill_ram_bytes or 0,
                dir_path=spill_dir,
                dir_bytes=spill_dir_bytes,
            )
            if spill_on
            else None
        )
        if self._spill is not None:
            self.prefix.on_evict = self._demote
        self._mirror: dict[str, list] = {}  # hash -> per-leaf page bytes
        self._mirror_refs: dict[str, int] = {}
        # restores are a host decision at admission but a DEVICE write on
        # the worker: plan_row queues (page_ids, per-leaf host arrays) and
        # the worker flushes them before the next prefill touches the
        # cache. Each pending item holds its own pool refs, so an eviction
        # racing the flush is harmless (the write lands in held pages).
        self._pending_restores: list = []
        self._restore_fns: dict = {}
        self.spill_restores = 0
        self.restore_skipped = 0
        self.restore_aborted = 0
        # ---- live KV handoff (ISSUE 20) -----------------------------------
        # pages held by adopt-queued restores not yet flushed to the
        # device: in-transit handoff pages that must read as HELD, not
        # leaked, in drain/leak accounting (mirroring prefix_held)
        self._handoff_pending = 0
        self.handoff_exports = 0
        self.handoff_adopted_pages = 0
        self.handoff_adopt_aborted = 0
        self.spill_skipped = 0  # demotes with missing mirror bytes
        self.mirror_capture_failures = 0
        # 0, not the post-heal value: startup quarantines surface on the
        # first opportunistic delta observation
        self._quarantined_seen = 0

    # ------------------------------------------------------------- helpers
    def _observe(self, event: str, **ctx) -> None:
        if self._observer is None:
            return
        try:
            self._observer(event, **ctx)
        except Exception:  # noqa: BLE001 — telemetry must not break serving
            pass

    def _pages_changed(self) -> None:
        self._observe(
            "kv_pages",
            used=self.pool.used,
            total=self.pool.n_pages,
            prefix_held=(
                self.prefix.held_pages if self.prefix is not None else 0
            ),
            handoff_held=self._handoff_pending,
        )

    @property
    def dense_equivalent_rows(self) -> int:
        """How many concurrent rows the SAME memory budget supports under
        dense worst-case reservation (seq_len slots per row) — the
        baseline the paged admission beats."""
        slots = self.layout.pool_pages * self.layout.page_tokens
        return max(1, slots // int(self.module.cfg.seq_len))

    # ----------------------------------------------------------- admission
    def plan_row(
        self,
        tokens,
        max_new: int,
        prompt_ladder: tuple,
        new_ladder: tuple,
        seq_len: int,
        trace=None,
    ) -> RowPlan:
        """Admit one row: prefix lookup + suffix bucketing + reservation.
        Raises ServingError (400) when the row can NEVER fit the pool and
        ShedError(reason="kv_pages") (503) when it cannot fit NOW.

        `trace` (telemetry.tracing.RequestTrace) receives a zero-duration
        `kv_plan` annotation with the admission decision — this module
        stays clock-free (lint rule 4), the clock read happens inside
        telemetry."""
        from .batching import choose_buckets

        pt = self.layout.page_tokens
        with self._lock:
            L, ppages, entry = 0, (), None
            if self.prefix is not None:
                if self._spill is not None:
                    # restore a spilled prefix BEFORE the lookup, so the
                    # lookup below hits it and the hit/miss ledger stays
                    # honest about what the request actually got
                    self._maybe_restore(tokens, len(tokens) - 1)
                # cap at len-1: prefill needs >= 1 suffix token to produce
                # the first sampled logits
                L, ppages, entry = self.prefix.lookup(
                    tokens, max_tokens=len(tokens) - 1
                )
                self._observe(
                    "prefix_hit" if entry is not None else "prefix_miss",
                    tokens=L,
                )
            try:
                sfx = len(tokens) - L
                pb, nb = choose_buckets(
                    sfx, max_new, prompt_ladder, new_ladder, seq_len - L
                )
                n_pages = self.layout.pages_for(L + pb + nb - 1)
                demand = n_pages - L // pt
                # scratch is permanently allocated → usable = pool - 1
                if demand + L // pt + 1 > self.pool.n_pages:
                    raise ServingError(
                        f"request needs {demand + L // pt} KV pages but the "
                        f"pool holds {self.pool.n_pages - 1} usable pages — "
                        f"raise kvPoolPages or shorten the request"
                    )
                try:
                    self.pool.reserve(demand)
                except PagePoolExhausted:
                    # make room: LRU-evict idle prefix entries, retry once
                    if self.prefix is None or not self.prefix.evict_for(demand):
                        raise
                    self._observe("prefix_evict")
                    self.pool.reserve(demand)
            except PagePoolExhausted as e:
                if entry is not None:
                    self.prefix.release(entry, ppages)
                self._observe("shed", reason="kv_pages")
                raise ShedError(
                    f"KV page pool exhausted: {e}",
                    reason="kv_pages",
                ) from None
            except ServingError:
                if entry is not None:
                    self.prefix.release(entry, ppages)
                raise
            self.active_rows += 1
            self.active_rows_hwm = max(self.active_rows_hwm, self.active_rows)
            self._pages_changed()
            if trace is not None:
                trace.annotate(
                    "kv_plan",
                    prefix_len=L,
                    prefix_hit=entry is not None,
                    suffix_bucket=pb,
                    new_bucket=nb,
                    pages=n_pages,
                    reserved=demand,
                )
            return RowPlan(
                prefix_len=L,
                prefix_pages=tuple(ppages),
                prefix_entry=entry,
                suffix_bucket=pb,
                new_bucket=nb,
                n_pages=n_pages,
                reserved=demand,
            )

    def release(self, plan: RowPlan) -> None:
        """Return everything a row holds: allocated pages, the unused
        remainder of its reservation, and its prefix references.
        Idempotent — wired to PendingRequest.on_finish, which fires on
        every terminal path (success, shed, deadline, crash, drain)."""
        with self._lock:
            if plan.released:
                return
            plan.released = True
            if plan.own_pages:
                self.pool.unref(plan.own_pages)
            if plan.reserved:
                self.pool.unreserve(plan.reserved)
            if plan.prefix_entry is not None:
                self.prefix.release(plan.prefix_entry, plan.prefix_pages)
            self.active_rows -= 1
            self._pages_changed()

    # ------------------------------------------------------ decode support
    def ensure_pages(self, plans, upto_slot: int, traces=None) -> None:
        """Allocate each plan's own pages to cover slots [0, upto_slot)
        out of its reservation. Called by the decode worker before
        prefill / each chunk — cannot fail (reserved <= free invariant).
        `traces` (parallel to `plans`) gets a `kv_ensure` annotation per
        row that actually allocated."""
        pt = self.layout.page_tokens
        with self._lock:
            for i, plan in enumerate(plans):
                if plan is None:
                    continue
                need_total = min(self.layout.pages_for(upto_slot), plan.n_pages)
                need = need_total - plan.prefix_pages_n - len(plan.own_pages)
                if need <= 0:
                    continue
                ids = self.pool.alloc(need, reserved=True)
                plan.reserved -= need
                plan.own_pages.extend(ids)
                if traces is not None and traces[i] is not None:
                    traces[i].annotate(
                        "kv_ensure", pages=need, upto_slot=upto_slot
                    )
            self._pages_changed()

    def tables(self, plans, batch: int, n_pages: int):
        """[batch, n_pages] int32 page tables: prefix + own pages per real
        row, scratch everywhere else (unallocated tails, dummy rows).

        The scratch tail is load-bearing for chunked prefill (ISSUE 14):
        the step engine requests tables WIDER than a row's allocated
        pages (the next power of two over its final page count, so one
        compiled program serves every chunk). Slots past the row's
        frontier are masked by `prompt_lengths`/position math inside the
        programs, so writes land in the scratch page and reads never
        reach it — any other fill value here would silently break the
        chunked ≡ one-shot byte-identity pin."""
        import numpy as np

        t = np.full((batch, n_pages), self.scratch, np.int32)
        with self._lock:
            for i, plan in enumerate(plans):
                if plan is None:
                    continue
                ids = list(plan.prefix_pages) + plan.own_pages
                t[i, : len(ids)] = ids
        return t

    # -------------------------------------------------------------- harvest
    def _harvest_fn(self, count: int, n_new: int):
        """Compiled pool-to-pool copy: gather `count` slots of one row's
        window (starting at traced slot `start`) and scatter them into
        `n_new` freshly allocated pages. Cache donated → in-place."""
        key = (count, n_new)
        fn = self._harvest_fns.get(key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        pt = self.layout.page_tokens

        def leaf4(pool, table_row, start, new_ids):
            slots = start + jnp.arange(count)
            vals = pool[table_row[slots // pt], slots % pt]
            vals = vals.reshape(n_new, pt, *pool.shape[2:])
            return pool.at[new_ids].set(vals)

        # scan_layers stacks a leading layer dim on every leaf; dispatch on
        # the config, not leaf ndim — int8 pools carry 3-dim scale leaves
        # whose scanned form is 4-dim, so an ndim test misclassifies them
        scanned = bool(getattr(self.module.cfg, "scan_layers", False))

        def kv_harvest(cache, table_row, start, new_ids):
            return jax.tree.map(
                lambda p: (
                    jax.vmap(lambda lp: leaf4(lp, table_row, start, new_ids))(p)
                    if scanned
                    else leaf4(p, table_row, start, new_ids)
                ),
                cache,
            )

        fn = jax.jit(kv_harvest, donate_argnums=(0,))
        self._harvest_fns[key] = fn
        return fn

    def harvest(self, rows) -> int:
        """Index each completed row's page-aligned prompt prefix. `rows`
        is [(tokens, plan, pad)] or [(tokens, plan, pad, trace)] —
        called by the decode worker AFTER the group's tokens are out
        (harvest must not delay TTFT). Returns the number of entries
        inserted."""
        if self.prefix is None:
            return 0
        import jax.numpy as jnp
        import numpy as np

        pt = self.layout.page_tokens
        inserted = 0
        for row in rows:
            tokens, plan, pad = row[:3]
            trace = row[3] if len(row) > 3 else None
            if plan is None or plan.released:
                continue
            k = len(tokens) // pt  # full prompt pages
            Lp = plan.prefix_pages_n
            if k <= Lp:
                continue
            with self._lock:
                if self.prefix.contains(tokens[: k * pt]):
                    continue
                n_new = k - Lp
                if self.pool.available < n_new:
                    # demote idle LRU entries rather than dropping the
                    # newest prompt: the freed pages net out against the
                    # new entry's, so admission headroom is untouched —
                    # and with a spill tier the evicted bytes survive.
                    if not self.prefix.evict_for(n_new):
                        self.harvest_skipped += 1
                        continue
                new_ids = self.pool.alloc(n_new)
                table = list(plan.prefix_pages) + plan.own_pages
            count = n_new * pt
            fn = self._harvest_fn(count, n_new)
            self.cache = fn(
                self.cache,
                jnp.asarray(np.asarray(table, np.int32)),
                jnp.asarray(plan.prefix_len + int(pad), jnp.int32),
                jnp.asarray(np.asarray(new_ids, np.int32)),
            )
            # capture the harvested pages' host mirror NOW, on the worker
            # thread, from the freshly scattered pool — the spill tier
            # needs the bytes long after the device copy may be donated
            mirror_pages = None
            if self._spill is not None:
                try:
                    mirror_pages = self._capture_mirror(new_ids)
                except Exception:  # noqa: BLE001 — spill is best-effort
                    self.mirror_capture_failures += 1
            with self._lock:
                hashes = (
                    page_hashes(tokens[: k * pt], pt, self.prefix.hash_fn)
                    if self._spill is not None
                    else ()
                )
                if mirror_pages is not None:
                    for idx in range(n_new):
                        self._mirror.setdefault(
                            hashes[Lp + idx], mirror_pages[idx]
                        )
                # index every chain link so partial-overlap prompts hit too
                for j in range(Lp + 1, k + 1):
                    pages_j = tuple(plan.prefix_pages) + tuple(
                        new_ids[: j - Lp]
                    )
                    if self.prefix.insert(tokens[: j * pt], pages_j):
                        inserted += 1
                        self._mirror_ref(hashes[:j])
                self._mirror_gc(hashes)
                # drop the allocation refs — the entries hold their own
                self.pool.unref(new_ids)
                self._pages_changed()
            if trace is not None:
                trace.annotate("kv_harvest_row", pages=n_new)
        return inserted

    # ------------------------------------------------- tiered spill (ISSUE 17)
    def _mirror_ref(self, hashes) -> None:
        for h in hashes:
            self._mirror_refs[h] = self._mirror_refs.get(h, 0) + 1

    def _mirror_unref(self, hashes) -> None:
        for h in hashes:
            c = self._mirror_refs.get(h)
            if c is None:
                continue
            if c <= 1:
                del self._mirror_refs[h]
                self._mirror.pop(h, None)
            else:
                self._mirror_refs[h] = c - 1

    def _mirror_gc(self, hashes) -> None:
        """Drop mirror bytes populated for positions no entry ended up
        covering (insert lost a collision race)."""
        for h in hashes:
            if h not in self._mirror_refs:
                self._mirror.pop(h, None)

    def _capture_mirror(self, new_ids) -> list:
        """Host copies of freshly written pool pages, per page per leaf.
        Runs on the worker thread right after the producing program
        returned — the only moment the bytes are guaranteed readable
        before some later donated program invalidates the buffer."""
        import jax
        import numpy as np

        scanned = bool(getattr(self.module.cfg, "scan_layers", False))
        ids = np.asarray(new_ids, np.int32)
        host = [
            np.asarray(leaf[:, ids] if scanned else leaf[ids])
            for leaf in jax.tree.leaves(self.cache)
        ]
        return [
            [h[:, i] if scanned else h[i] for h in host]
            for i in range(len(new_ids))
        ]

    def _observe_quarantine(self) -> None:
        q = self._spill.quarantined
        if q > self._quarantined_seen:
            self._observe("kv_spill_quarantined", n=q - self._quarantined_seen)
            self._quarantined_seen = q

    def _demote(self, h: str, e: PrefixEntry) -> None:
        """PrefixCache eviction hook: move the entry's bytes to the spill
        tier instead of losing them. Runs under self._lock (every evict
        path is inside a locked region) with the pages still referenced."""
        hashes = page_hashes(e.tokens, self.layout.page_tokens, self.prefix.hash_fn)
        try:
            pages = []
            for hj in hashes:
                b = self._mirror.get(hj)
                if b is None:
                    # mirror capture failed/never happened for a position —
                    # the entry just evicts the pre-spill way
                    self.spill_skipped += 1
                    pages = None
                    break
                pages.append(b)
            if pages is not None:
                payload = SpillPayload(tuple(e.tokens), tuple(hashes), pages)
                if self._spill.put(payload):
                    self._observe("kv_spill", bytes=payload.nbytes)
                self._observe_quarantine()
        finally:
            self._mirror_unref(hashes)

    def _maybe_restore(self, tokens, limit: int) -> None:
        """Admission-time restore: if the spill tier holds a LONGER
        verified prefix of `tokens` than the in-pool cache, pull its
        pages back into the pool and re-index every chain link, so the
        lookup that follows hits it. Caller holds self._lock."""
        pt = self.layout.page_tokens
        hashes = page_hashes(tokens[:limit], pt, self.prefix.hash_fn)
        if not hashes:
            return
        _k_len, k_pages = self.prefix.peek(tokens, max_tokens=limit)
        k = len(k_pages)
        j = 0
        for cand in range(len(hashes), k, -1):
            if self._spill.has(hashes[cand - 1], tokens[: cand * pt]):
                j = cand
                break
        if j == 0:
            return
        n_new = j - k
        # same headroom rule as harvest: cache warmth never eats the
        # admission headroom a reservation is about to need
        if self.pool.available < n_new:
            self.restore_skipped += 1
            return
        payload = self._spill.take(hashes[j - 1], tokens[: j * pt])
        self._observe_quarantine()
        if payload is None:
            # corrupt/incomplete segment — quarantined, clean miss
            return
        try:
            new_ids = self.pool.alloc(n_new)
        except PagePoolExhausted:
            self.restore_skipped += 1
            return
        queued = None
        try:
            # chaos: a kill here is a death mid-restore — the except arm
            # below must return every page this restore holds (zero-leak)
            inject("kv.restore", h=hashes[j - 1], pages=n_new)
            queued = self._queue_restore(new_ids, payload.pages[k:])
            for pos in range(1, j + 1):
                self._mirror.setdefault(hashes[pos - 1], payload.pages[pos - 1])
            inserted = 0
            for jj in range(k + 1, j + 1):
                pages_jj = tuple(k_pages) + tuple(new_ids[: jj - k])
                if self.prefix.insert(tokens[: jj * pt], pages_jj):
                    inserted += 1
                    self._mirror_ref(hashes[:jj])
            self._mirror_gc(hashes)
            if inserted == 0:
                # lost the admission race (hash slot taken by different
                # content): cancel the queued device write, free its pages
                self._unqueue_restore(queued)
                queued = None
                self.restore_aborted += 1
            else:
                self.spill_restores += 1
                self._observe("kv_spill_restore", pages=n_new)
            self.pool.unref(new_ids)
            self._pages_changed()
        except BaseException:
            if queued is not None:
                self._unqueue_restore(queued)
            self.pool.unref(new_ids)
            raise

    def _queue_restore(self, new_ids, pages_payload, tag: str = "spill") -> tuple:
        """Queue the device write for restored pages. The item holds its
        OWN pool refs, so an eviction racing the flush is harmless — the
        write lands in still-held pages, which free right after.
        `tag="handoff"` items additionally count into `_handoff_pending`
        (the in-transit page gauge) until flushed."""
        import numpy as np

        scanned = bool(getattr(self.module.cfg, "scan_layers", False))
        n_leaves = len(pages_payload[0])
        vals = [
            np.stack(
                [page[l] for page in pages_payload],
                axis=1 if scanned else 0,
            )
            for l in range(n_leaves)
        ]
        self.pool.ref(new_ids)
        item = (list(new_ids), vals, tag)
        self._pending_restores.append(item)
        if tag == "handoff":
            self._handoff_pending += len(new_ids)
        return item

    def _unqueue_restore(self, item) -> bool:
        """Cancel one queued restore (abort path): drop it from the
        pending list and return its refs. Caller holds self._lock."""
        try:
            self._pending_restores.remove(item)
        except ValueError:
            return False
        self.pool.unref(item[0])
        if item[2] == "handoff":
            self._handoff_pending -= len(item[0])
        return True

    def _restore_fn(self, n_new: int):
        """Compiled scatter of `n_new` restored pages into the pool
        (cache donated → in place), keyed like _harvest_fn."""
        fn = self._restore_fns.get(n_new)
        if fn is not None:
            return fn
        import jax

        scanned = bool(getattr(self.module.cfg, "scan_layers", False))

        def kv_restore(cache, ids, vals):
            leaves, treedef = jax.tree.flatten(cache)
            out = [
                (leaf.at[:, ids].set(v) if scanned else leaf.at[ids].set(v))
                for leaf, v in zip(leaves, vals)
            ]
            return jax.tree.unflatten(treedef, out)

        fn = jax.jit(kv_restore, donate_argnums=(0,))
        self._restore_fns[n_new] = fn
        return fn

    def flush_restores(self) -> int:
        """Apply queued restore writes to the device pool. The decode
        worker calls this right before a prefill dispatch (under the
        server lock), so a restored row's first read sees its bytes.
        Returns the number of restore batches applied."""
        with self._lock:
            if not self._pending_restores:
                return 0
            pending, self._pending_restores = self._pending_restores, []
        import jax.numpy as jnp
        import numpy as np

        done = 0
        for ids, vals, tag in pending:
            fn = self._restore_fn(len(ids))
            self.cache = fn(
                self.cache,
                jnp.asarray(np.asarray(ids, np.int32)),
                [jnp.asarray(v) for v in vals],
            )
            done += 1
            with self._lock:
                self.pool.unref(ids)
                if tag == "handoff":
                    self._handoff_pending -= len(ids)
                self._pages_changed()
        return done

    # ------------------------------------------------- live handoff (ISSUE 20)
    def export_prefix(self, tokens) -> Optional[SpillPayload]:
        """Capture the longest cached page-aligned prefix of `tokens` as
        a host SpillPayload — the wire unit of the live KV handoff.

        WORKER THREAD ONLY, right after the producing program returned
        (same contract as `_capture_mirror`): that is the one moment the
        pool bytes are guaranteed readable before a later donated
        program invalidates them. The chain pages are ref-held across
        the device read so a racing eviction cannot recycle them
        mid-capture. Returns None when nothing page-aligned is cached
        (prompt shorter than a page, prefix cache off) — the caller
        falls back to monolithic decode."""
        if self.prefix is None:
            return None
        pt = self.layout.page_tokens
        k = len(tokens) // pt
        if k < 1:
            return None
        with self._lock:
            _plen, page_ids = self.prefix.peek(tokens, max_tokens=k * pt)
            j = len(page_ids)
            if j < 1:
                return None
            page_ids = list(page_ids)
            self.pool.ref(page_ids)
        try:
            pages = self._capture_mirror(page_ids)
        finally:
            with self._lock:
                self.pool.unref(page_ids)
                self._pages_changed()
        hashes = page_hashes(tokens[: j * pt], pt, self.prefix.hash_fn)
        with self._lock:
            self.handoff_exports += 1
        return SpillPayload(
            tuple(int(t) for t in tokens[: j * pt]), tuple(hashes), pages
        )

    def adopt_pages(self, payload: SpillPayload) -> int:
        """Adopt an imported handoff page set: allocate pool pages,
        queue the device write (flushed by the worker before the next
        prefill, exactly like a spill restore), and index every chain
        link in the prefix cache so the failed-over request's admission
        hits it. Content verification (CRC frames + hash chain vs the
        prompt tokens) is the HTTP layer's job — this method owns the
        refcount/reservation invariants only.

        Returns the number of newly adopted pages (0 when the chain is
        already resident — a repeated import is idempotent). Raises
        ShedError(reason="kv_handoff") when there is no headroom even
        after LRU eviction: cache warmth never eats admission headroom,
        and the exporter's fallback path is cheaper than an OOM here.
        Every abort path — chaos raise, collision race, headroom shed —
        returns every page this adoption holds (zero-leak)."""
        if self.prefix is None:
            raise ServingError("kv handoff requires the prefix cache")
        pt = self.layout.page_tokens
        tokens = tuple(int(t) for t in payload.tokens)
        j = len(payload.pages)
        with self._lock:
            _plen, k_pages = self.prefix.peek(tokens, max_tokens=len(tokens))
            k = len(k_pages)
            n_new = j - k
            if n_new <= 0:
                return 0
            if self.pool.available < n_new:
                if not self.prefix.evict_for(n_new):
                    self._observe("shed", reason="kv_handoff")
                    raise ShedError(
                        f"KV pool cannot adopt {n_new} handoff pages "
                        f"({self.pool.available} free)",
                        reason="kv_handoff",
                    )
                self._observe("prefix_evict")
            try:
                new_ids = self.pool.alloc(n_new)
            except PagePoolExhausted as e:
                self._observe("shed", reason="kv_handoff")
                raise ShedError(
                    f"KV pool cannot adopt handoff pages: {e}",
                    reason="kv_handoff",
                ) from None
            queued = None
            try:
                # chaos: a kill here is a death mid-adopt — the except
                # arm must return every page this adoption holds
                inject("serving.kv_adopt", h=payload.hashes[-1], pages=n_new)
                queued = self._queue_restore(
                    new_ids, payload.pages[k:], tag="handoff"
                )
                if self._spill is not None:
                    for pos in range(1, j + 1):
                        self._mirror.setdefault(
                            payload.hashes[pos - 1], payload.pages[pos - 1]
                        )
                inserted = 0
                for jj in range(k + 1, j + 1):
                    pages_jj = tuple(k_pages) + tuple(new_ids[: jj - k])
                    if self.prefix.insert(tokens[: jj * pt], pages_jj):
                        inserted += 1
                        if self._spill is not None:
                            self._mirror_ref(payload.hashes[:jj])
                if self._spill is not None:
                    self._mirror_gc(payload.hashes)
                if inserted == 0:
                    # collision race: different content owns the chain
                    # slots — cancel the queued write, free the pages
                    self._unqueue_restore(queued)
                    queued = None
                    self.handoff_adopt_aborted += 1
                    n_new = 0
                else:
                    self.handoff_adopted_pages += n_new
                    self._observe("kv_handoff_adopt", pages=n_new)
                self.pool.unref(new_ids)
                self._pages_changed()
                return n_new
            except BaseException:
                if queued is not None:
                    self._unqueue_restore(queued)
                self.pool.unref(new_ids)
                self._pages_changed()
                raise

    def advertised_heads(self) -> list[str]:
        """Chain hashes restorable on this replica — resident PrefixCache
        entries plus spilled entries in either tier. The /kvz payload."""
        with self._lock:
            heads = self.prefix.heads() if self.prefix is not None else []
            if self._spill is not None:
                heads.extend(self._spill.heads())
            return list(dict.fromkeys(heads))

    # ---------------------------------------------------------------- stats
    def kv_pool_bytes(self) -> int:
        """Actual HBM bytes of the device pool pytree (payload + scales) —
        measured off the live leaves, so it is exact for any layout/quant
        combination and matches models/quant.kv_pool_bytes by construction."""
        import jax

        return int(
            sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(self.cache))
        )

    def stats(self) -> dict:
        with self._lock:
            out = {
                "page_tokens": self.layout.page_tokens,
                "kv_quant": self.layout.kv_quant,
                "kv_pool_bytes": self.kv_pool_bytes(),
                "pages_total": self.pool.n_pages,
                "pages_used": self.pool.used,
                "pages_reserved": self.pool.reserved,
                "pages_hwm": self.pool.used_hwm,
                "active_rows": self.active_rows,
                "active_rows_hwm": self.active_rows_hwm,
                "dense_equivalent_rows": self.dense_equivalent_rows,
                "harvest_skipped": self.harvest_skipped,
            }
            if self.prefix is not None:
                out["prefix"] = {
                    "entries": len(self.prefix),
                    "page_refs": self.prefix.page_refs,
                    "hits": self.prefix.hits,
                    "misses": self.prefix.misses,
                    "evictions": self.prefix.evictions,
                    "collisions": self.prefix.collisions,
                }
            if (
                self.handoff_exports
                or self.handoff_adopted_pages
                or self.handoff_adopt_aborted
                or self._handoff_pending
            ):
                out["handoff"] = {
                    "exports": self.handoff_exports,
                    "adopted_pages": self.handoff_adopted_pages,
                    "adopt_aborted": self.handoff_adopt_aborted,
                    "pending_pages": self._handoff_pending,
                }
            if self._spill is not None:
                out["spill"] = {
                    **self._spill.stats(),
                    "restores": self.spill_restores,
                    "restore_skipped": self.restore_skipped,
                    "restore_aborted": self.restore_aborted,
                    "spill_skipped": self.spill_skipped,
                    "mirror_entries": len(self._mirror),
                    "mirror_capture_failures": self.mirror_capture_failures,
                    "pending_restores": len(self._pending_restores),
                }
            return out
