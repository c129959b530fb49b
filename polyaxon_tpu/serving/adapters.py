"""Hot-swapped LoRA adapter multiplexing for multi-tenant serving (ISSUE 19).

One base model, many tenants, each with its own LoRA adapter. The model
side (models/transformer.py `adapter_slots`) stacks every `lora_a`/`lora_b`
pair to [slots, ...] and gathers a PER-ROW adapter by index, so one
coalesced decode group mixes tenants; this module owns the slots:

* `stack_adapter_params` — load-time tree surgery (the quantize-on-load
  sibling): rebuild the module with `adapter_slots = N + 1` and stack the
  restored checkpoint's adapters so SLOT 0 carries the checkpoint's own
  lora_a/lora_b (the base/resident adapter every default-tenant and pad
  row rides — serving behavior without a tenant header is unchanged) and
  slots 1..N start as zero adapters (lora_b = 0 ⇒ delta = 0) for the
  registry to fill.
* `AdapterRegistry` — manages slots 1..N like KV pages: refcounted
  residency (a slot is pinned while any in-flight row gathers it), LRU
  eviction of idle adapters when a request needs a slot, demotion of the
  evicted weights through the PR 17 SpillManager tiers (host-RAM LRU →
  CRC-framed disk segments) keyed `adapter:<name>`, and restore-on-request
  (a spilled adapter's next acquire restores the exact bytes instead of
  re-reading the source). Counters: `serving_adapter_loads_total`,
  `serving_adapter_evictions_total`, `serving_adapter_restores_total`
  and the `serving_adapter_resident` gauge.

Adapter sources are either an `.npz` file (keys = slash-joined param
paths, e.g. ``layer_0/attention/q_proj/lora_a``; `save_adapter` writes
this format) or the deterministic synthesizer ``seed:<int>`` (tests
use it — same seed, same bytes, anywhere).

The device-resident copy of an adapter IS its slot slice of the stacked
params — the registry never holds a second device copy. It reads/writes
slots through two injected callbacks (`read_slot`/`write_slot`) so the
owning ModelServer keeps the params swap under its own compile lock;
lock order is registry lock → server lock, never the reverse.

NO wall clocks in here (scripts/lint_telemetry.py rule 16): residency
recency is a logical sequence number, and load/restore latency is timed
by the serving layer around `acquire()`, where clocks are allowed.
"""

from __future__ import annotations

import dataclasses
import threading
import zlib
from typing import Any, Callable, Optional

import numpy as np

from ..chaos.injector import inject
from .batching import ShedError
from .spill import SpillManager, SpillPayload

__all__ = [
    "AdapterRegistry",
    "adapter_template",
    "load_adapter",
    "save_adapter",
    "stack_adapter_params",
    "synth_adapter",
]


def _is_mapping(x: Any) -> bool:
    return hasattr(x, "items") and not hasattr(x, "shape")


def stack_adapter_params(module, params, *, slots: int):
    """Rebuild `module` with `adapter_slots = slots` and stack the params
    tree to match: every ``lora_a`` broadcasts to all slots (A values are
    inert wherever B is zero) and every ``lora_b`` keeps the checkpoint's
    value at slot 0 with zeros in slots 1.. (the permanent zero adapters
    the registry hot-swaps). Returns (module, params).

    Handles both layouts: per-layer leaves ``[in, r]`` and nn.scan-stacked
    leaves ``[layers, in, r]`` — the slot axis lands at ndim-3 of the new
    leaf either way, matching what LoRADense (and nn.scan above it)
    creates."""
    import jax.numpy as jnp

    cfg = getattr(module, "cfg", None)
    if cfg is None or getattr(cfg, "lora_rank", 0) <= 0:
        raise ValueError(
            "adapter multiplexing needs a LoRA model (lora_rank > 0): "
            "there are no adapter params to stack"
        )
    if getattr(cfg, "adapter_slots", 0) > 0:
        raise ValueError(
            "params are already slot-stacked (adapter_slots = "
            f"{cfg.adapter_slots}) — stack-on-load runs once"
        )
    if slots < 2:
        raise ValueError("adapter stacking needs slots >= 2 (slot 0 is the base adapter)")

    def walk(tree):
        out = {}
        for k, v in tree.items():
            if _is_mapping(v):
                out[k] = walk(v)
            elif k == "lora_a":
                a = jnp.asarray(v)
                out[k] = jnp.broadcast_to(
                    a[..., None, :, :], (*a.shape[:-2], slots, *a.shape[-2:])
                )
            elif k == "lora_b":
                b = jnp.asarray(v)
                zeros = jnp.zeros(
                    (*b.shape[:-2], slots - 1, *b.shape[-2:]), b.dtype
                )
                out[k] = jnp.concatenate(
                    [b[..., None, :, :], zeros], axis=-3
                )
            else:
                out[k] = v
        return out

    new_module = type(module)(dataclasses.replace(cfg, adapter_slots=slots))
    return new_module, walk(params)


def adapter_template(params) -> dict:
    """Slash-joined path → (shape, dtype) for every slot-stacked adapter
    leaf, with the slot axis removed — the shape ONE adapter's arrays
    must have. Paths are sorted, and every demote/restore walks them in
    this order, so spilled payloads always round-trip positionally."""
    out: dict[str, tuple] = {}

    def walk(tree, prefix):
        for k in sorted(tree):
            v = tree[k]
            if _is_mapping(v):
                walk(v, prefix + (k,))
            elif k in ("lora_a", "lora_b"):
                shape = tuple(v.shape[:-3]) + tuple(v.shape[-2:])
                out["/".join(prefix + (k,))] = (shape, np.dtype(str(v.dtype)))

    walk(params, ())
    if not out:
        raise ValueError("no slot-stacked lora_a/lora_b leaves in params")
    return dict(sorted(out.items()))


def synth_adapter(template: dict, seed: int) -> dict:
    """Deterministic synthetic adapter: same (seed, path) → same bytes on
    any host (the stream is keyed by crc32 of the path, never by
    PYTHONHASHSEED). lora_b is NON-zero so the adapter visibly changes
    outputs — that is what the byte-identity tests multiplex on."""
    out = {}
    for path, (shape, dtype) in template.items():
        rng = np.random.default_rng([int(seed), zlib.crc32(path.encode())])
        out[path] = rng.normal(0.0, 0.05, shape).astype(dtype)
    return out


def save_adapter(path, adapter: dict) -> None:
    """Write an adapter dict (slash-joined paths → arrays) as .npz —
    the on-disk format `load_adapter` and the CLI `--adapter name=file`
    flag consume."""
    np.savez(path, **{k: np.asarray(v) for k, v in adapter.items()})


def load_adapter(source: str, template: dict) -> dict:
    """Materialize an adapter from its source: ``seed:<int>`` synthesizes
    deterministically, anything else loads as .npz. Shapes/dtypes are
    validated against the template — a wrong-shape adapter must fail the
    load, not corrupt a slot."""
    if source.startswith("seed:"):
        return synth_adapter(template, int(source[len("seed:"):]))
    with np.load(source) as z:
        found = {k: np.asarray(z[k]) for k in z.files}
    out = {}
    for path, (shape, dtype) in template.items():
        if path not in found:
            raise ValueError(f"adapter {source!r} is missing leaf {path!r}")
        arr = found[path]
        if tuple(arr.shape) != tuple(shape):
            raise ValueError(
                f"adapter {source!r} leaf {path!r} has shape "
                f"{tuple(arr.shape)}, model expects {tuple(shape)}"
            )
        out[path] = arr.astype(dtype, copy=False)
    return out


@dataclasses.dataclass
class _Entry:
    name: str
    source: str
    slot: Optional[int] = None
    refs: int = 0
    seq: int = 0  # logical recency (LRU order among idle residents)
    loads: int = 0


class AdapterRegistry:
    """Refcounted residency manager for adapter slots 1..n_slots.

    `acquire(name)` pins the adapter's slot for one in-flight row and
    returns the slot index; `release(name)` unpins it (the serving layer
    chains release onto the request's idempotent finish, so a slot is
    never freed while a batch still gathers it). A miss loads the
    adapter into a free slot — evicting the least-recently-used IDLE
    adapter when full, demoting its weights to the spill tiers — and a
    spilled adapter restores its exact bytes on the next acquire.
    With every slot pinned, acquire sheds (`reason: adapter_capacity`)
    instead of blocking the decode worker.

    Thread-safe; clock-free (logical seq counter for recency)."""

    def __init__(
        self,
        *,
        slots: int,
        sources: dict,
        template: dict,
        read_slot: Callable[[int], list],
        write_slot: Callable[[int, dict], None],
        spill: Optional[SpillManager] = None,
        telemetry=None,
    ):
        if slots < 1:
            raise ValueError("AdapterRegistry needs at least 1 adapter slot")
        self.n_slots = int(slots)
        self.template = dict(template)
        self._paths = sorted(self.template)
        self._read_slot = read_slot
        self._write_slot = write_slot
        self._spill = spill
        self._lock = threading.RLock()
        self._seq = 0
        self._entries: dict[str, _Entry] = {
            str(name): _Entry(str(name), str(src))
            for name, src in dict(sources).items()
        }
        self._by_slot: dict[int, str] = {}
        # cumulative counters (also exported through `telemetry`)
        self.loads = 0
        self.evictions = 0
        self.restores = 0
        self._m_loads = self._m_evict = self._m_restore = None
        self._g_resident = None
        if telemetry is not None:
            self._m_loads = telemetry.counter(
                "serving.adapter_loads",
                help="Adapter weight loads from source into a slot",
            )
            self._m_evict = telemetry.counter(
                "serving.adapter_evictions",
                help="Idle adapters evicted from their slot (LRU)",
            )
            self._m_restore = telemetry.counter(
                "serving.adapter_restores",
                help="Adapter loads served from the spill tiers",
            )
            self._g_resident = telemetry.gauge(
                "serving.adapter_resident",
                help="Adapters currently resident in a slot",
            )
            self._g_resident.set(0.0)

    # -------------------------------------------------------------- views
    def known(self) -> list:
        return sorted(self._entries)

    def resident(self) -> dict:
        with self._lock:
            return {
                e.name: e.slot for e in self._entries.values()
                if e.slot is not None
            }

    def refcount(self, name: str) -> int:
        with self._lock:
            return self._entries[name].refs

    def stats(self) -> dict:
        with self._lock:
            return {
                "slots": self.n_slots,
                "resident": sum(
                    1 for e in self._entries.values() if e.slot is not None
                ),
                "loads": self.loads,
                "evictions": self.evictions,
                "restores": self.restores,
                "adapters": {
                    e.name: {
                        "slot": e.slot,
                        "refs": e.refs,
                        "source": e.source,
                        "state": (
                            "resident" if e.slot is not None
                            else "spilled" if self._spilled(e.name)
                            else "cold"
                        ),
                    }
                    for e in sorted(
                        self._entries.values(), key=lambda e: e.name
                    )
                },
            }

    def check_invariants(self) -> None:
        """Every slot maps to at most one adapter and the maps agree —
        the chaos tests assert this after a kill mid-restore."""
        with self._lock:
            for slot, name in self._by_slot.items():
                e = self._entries[name]
                assert e.slot == slot, (name, slot, e.slot)
            slots = [e.slot for e in self._entries.values() if e.slot is not None]
            assert len(slots) == len(set(slots)), slots
            assert all(1 <= s <= self.n_slots for s in slots), slots

    def _spilled(self, name: str) -> bool:
        return self._spill is not None and self._spill.has(
            f"adapter:{name}", ()
        )

    # ------------------------------------------------------------ acquire
    def acquire(self, name: str) -> tuple:
        """Pin `name`'s adapter and return (slot, loaded) — `loaded` True
        when this call brought the weights into the slot (the serving
        layer times exactly those acquires into the adapter-load
        histogram). Raises KeyError for an unknown adapter and ShedError
        (`adapter_capacity`) when every slot is pinned by in-flight
        rows."""
        with self._lock:
            e = self._entries[name]  # KeyError → serving 400 upstream
            self._seq += 1
            e.seq = self._seq
            if e.slot is not None:
                e.refs += 1
                return e.slot, False
            slot = self._free_slot()
            if slot is None:
                raise ShedError(
                    f"all {self.n_slots} adapter slots are pinned by "
                    "in-flight requests",
                    reason="adapter_capacity",
                    retry_after_s=0.5,
                )
            self._load_into(e, slot)
            e.slot = slot
            e.refs = 1
            self._by_slot[slot] = name
            if self._g_resident is not None:
                self._g_resident.set(float(len(self._by_slot)))
            return slot, True

    def release(self, name: str) -> None:
        with self._lock:
            e = self._entries.get(name)
            if e is not None and e.refs > 0:
                e.refs -= 1

    # ------------------------------------------------------------ internal
    def _free_slot(self) -> Optional[int]:
        for s in range(1, self.n_slots + 1):
            if s not in self._by_slot:
                return s
        # no free slot: evict the least-recently-used IDLE resident
        idle = [
            e for e in self._entries.values()
            if e.slot is not None and e.refs == 0
        ]
        if not idle:
            return None
        victim = min(idle, key=lambda e: e.seq)
        return self._evict(victim)

    def _evict(self, victim: _Entry) -> int:
        slot = victim.slot
        assert slot is not None
        if self._spill is not None:
            arrays = [
                np.ascontiguousarray(a)
                for a in self._read_slot(slot)
            ]
            self._spill.put(SpillPayload(
                tokens=(), hashes=(f"adapter:{victim.name}",), pages=[arrays]
            ))
        victim.slot = None
        del self._by_slot[slot]
        self.evictions += 1
        if self._m_evict is not None:
            self._m_evict.inc()
        if self._g_resident is not None:
            self._g_resident.set(float(len(self._by_slot)))
        return slot

    def _load_into(self, e: _Entry, slot: int) -> None:
        """Bring `e`'s weights into `slot`: spill restore when available,
        source load otherwise. A failure mid-way (including an injected
        chaos kill) must leave the registry consistent — the slot stays
        free, the payload returns to the spill tier, and no refcount
        moved — so a crashed restore costs a retry, never a leak."""
        payload = None
        if self._spill is not None:
            payload = self._spill.take(f"adapter:{e.name}", ())
        try:
            # chaos: a kill here lands between take and the slot write —
            # the except arm re-spills the payload, zero-leak pinned by
            # tests/test_tenancy.py
            inject("serving.adapter_restore", name=e.name, slot=slot,
                   restored=payload is not None)
            if payload is not None:
                arrays = payload.pages[0]
                adapter = {
                    p: arrays[i] for i, p in enumerate(self._paths)
                }
                self._write_slot(slot, adapter)
                self.restores += 1
                if self._m_restore is not None:
                    self._m_restore.inc()
            else:
                adapter = load_adapter(e.source, self.template)
                self._write_slot(slot, adapter)
            self.loads += 1
            e.loads += 1
            if self._m_loads is not None:
                self._m_loads.inc()
        except BaseException:
            if payload is not None and self._spill is not None:
                self._spill.put(payload)
            raise
