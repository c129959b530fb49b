"""Which devices a process runs on, and saying so.

Plain `JAX_PLATFORMS` selects the platform. `POLYAXON_JAX_PLATFORM` (with
`POLYAXON_NUM_CPU_DEVICES`) exists for the two things it does not do:
provisioning N virtual CPU devices, and injection into gang workers, which
the executor starts through the native launcher. Both go through
`jax.config` before the first backend touch.

The rest of this module keeps a run honest about its device: the report
every trainer and server publishes (`device_report`), the error raised when
a spec that declares a TPU finds itself on the CPU without having been told
so (`require_declared_tpu`), the per-process chip assignment for replica
children (`chip_env`), and the persistent compilation cache's location.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional, Sequence

from ..retry import PermanentError

# One fixed place, inside the checkout and git-ignored: the path is part of
# JAX's cache key, so a directory that moves (a temp POLYAXON_HOME) never hits.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_compile_cache"

# TPU_CHIPS_PER_PROCESS_BOUNDS for the chip counts a v5e host (2x2) can give
# one process
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}
_TPU_PROCESS_PORT_BASE = 8476


class PlatformEnvError(Exception):
    pass


class PlatformMismatchError(PermanentError):
    """A spec that declares `environment.resources.tpu` is about to run on
    the CPU, and nothing in the environment asked for the CPU."""


def parse_n_cpu(value: Optional[str], source: str) -> int:
    if value is None:
        return 1
    try:
        return int(value.strip())
    except ValueError:
        raise PlatformEnvError(
            f"{source} must be an integer device count, got {value!r}"
        ) from None


def env_platform() -> Optional[str]:
    return os.environ.get("POLYAXON_JAX_PLATFORM") or None


def env_n_cpu() -> int:
    """POLYAXON_NUM_CPU_DEVICES with the JAX_NUM_CPU_DEVICES fallback — the
    same convention the executor forwards into gang workers, so in-process
    and gang runs see the same device count from the same environment."""
    for var in ("POLYAXON_NUM_CPU_DEVICES", "JAX_NUM_CPU_DEVICES"):
        raw = os.environ.get(var)
        if raw:
            return parse_n_cpu(raw, var)
    return 1


def apply_platform(platform: str, n_cpu: int = 1) -> None:
    """Select `platform` (provisioning `n_cpu` virtual devices when cpu)
    via jax.config. Raises RuntimeError if the backend is already up with a
    conflicting configuration — callers decide whether that is fatal."""
    import jax

    if platform == "cpu":
        jax.config.update("jax_num_cpu_devices", int(n_cpu))
    jax.config.update("jax_platforms", platform)


def apply_platform_env() -> Optional[str]:
    """Apply POLYAXON_JAX_PLATFORM / POLYAXON_NUM_CPU_DEVICES if set.
    Returns the platform applied, or None when the env asks for nothing."""
    platform = env_platform()
    if not platform:
        return None
    apply_platform(platform, env_n_cpu())
    return platform


def enable_cpu_collectives() -> None:
    """Route multi-process CPU collectives through gloo. The default
    XLA:CPU client refuses cross-process collectives ("Multiprocess
    computations aren't implemented on the CPU backend"); gloo ships in
    jaxlib and makes local CPU gangs run real collectives — which is what
    lets the distributed path be tested without a TPU slice. Must run
    before the backend initializes."""
    import jax

    jax.config.update("jax_cpu_collectives_implementation", "gloo")


# ------------------------------------------------------------ device honesty
def _platforms_named() -> list[str]:
    import jax

    return [
        env_platform() or "",
        os.environ.get("JAX_PLATFORMS", ""),
        jax.config.jax_platforms or "",  # what `apply_platform` wrote
    ]


def names_cpu(platforms: str) -> bool:
    """Does a JAX_PLATFORMS-style list make the CPU the default platform?
    Only its first entry does: `tpu,cpu` still means the TPU, and fails at
    start-up without one."""
    return platforms.split(",")[0].strip().lower() == "cpu"


def env_names_cpu() -> bool:
    """`names_cpu` of the environment alone — for parents that must not
    import JAX (POLYAXON_JAX_PLATFORM is applied over JAX_PLATFORMS)."""
    return names_cpu(env_platform() or os.environ.get("JAX_PLATFORMS", ""))


def cpu_requested() -> bool:
    """True when the CPU was asked for by name: POLYAXON_JAX_PLATFORM,
    JAX_PLATFORMS, or `jax.config.jax_platforms`."""
    return any(names_cpu(v) for v in _platforms_named())


def require_declared_tpu(run, platform: str) -> None:
    """A run whose spec declares `environment.resources.tpu` must not land
    on the CPU unasked: JAX falls back to the CPU when the TPU fails to
    initialise, Pallas kernels switch to interpret mode there, and the run
    would exit 0 having measured nothing."""
    env = getattr(run, "environment", None)
    tpu = env.resources.tpu if env and env.resources else None
    if tpu is None or platform != "cpu" or cpu_requested():
        return
    raise PlatformMismatchError(
        f"the spec declares environment.resources.tpu ({tpu.type}) but this "
        "process runs on the cpu and nothing asked for it: the TPU did not "
        "initialise, or a local gang was sent to virtual CPU devices. Fix "
        "the device, or say JAX_PLATFORMS=cpu / POLYAXON_JAX_PLATFORM=cpu "
        "to run this spec on the CPU on purpose"
    )


def device_report(devices: Sequence, module_cfg=None, seq_len=None) -> dict:
    """What a trainer or server runs on, for the run store and /statsz:
    platform, device kind, device ids, and — for a model that has one — the
    attention backend `auto` resolved to and whether Pallas kernels would
    run in interpret mode here."""
    out = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_ids": [int(d.id) for d in devices],
        # the chips of the host this process was given (`chip_env`): a
        # process that owns one chip of four still calls it device 0
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
    }
    backend = getattr(module_cfg, "attention", None)
    if backend is not None:
        if backend == "auto":
            from ..ops.attention import resolve_auto_backend

            backend = resolve_auto_backend(
                int(seq_len or module_cfg.seq_len),
                module_cfg.attention_block,
                module_cfg.head_size,
            )
        from ..ops.flash_attention import _interpret

        out["attention_backend"] = backend
        out["pallas_interpret"] = _interpret()
    return out


def device_memory(devices: Sequence) -> dict:
    """Per-device `memory_stats()` bytes, keyed by device id. Backends that
    report none (the CPU) give an empty dict."""
    out = {}
    for d in devices:
        stats = d.memory_stats() or {}
        if stats:
            out[str(d.id)] = {
                k: int(stats[k])
                for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                if k in stats
            }
    return out


# ------------------------------------------------------ one process per chip
def chip_env(slot: int, chips: int = 1) -> dict[str, str]:
    """Environment that gives the child process of replica `slot` exactly
    its own `chips` chips of the host (chips [slot*chips, (slot+1)*chips))
    and makes it a one-process TPU system of its own. Without it every
    child opens every chip of the host and the second one cannot start.
    Harmless off-TPU: only libtpu reads these."""
    if chips not in _CHIP_BOUNDS:
        raise ValueError(
            f"chips per replica must be one of {sorted(_CHIP_BOUNDS)}, "
            f"got {chips}"
        )
    first = slot * chips
    port = _TPU_PROCESS_PORT_BASE + slot
    return {
        "TPU_VISIBLE_CHIPS": ",".join(str(first + i) for i in range(chips)),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": _CHIP_BOUNDS[chips],
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
        "TPU_PROCESS_PORT": str(port),
        "CLOUD_TPU_TASK_ID": "0",
        # libtpu otherwise lets one process per host load it at a time
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }


# ------------------------------------------------------------- compile cache
def apply_compilation_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX has already taken the
    directory from it and this sets none. Otherwise an accelerator backend
    caches at `COMPILE_CACHE_DIR`; the CPU backend stays uncached (its AOT
    entries embed host CPU features, and CPU compiles are cheap). A first
    compile of a chip-sized step takes most of a minute, and every trainer,
    `serve` restart and replica child pays it again without this."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        if jax.default_backend() == "cpu":
            return None
        path = str(COMPILE_CACHE_DIR)
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # the default only caches compiles over 1 s; the small serving programs
    # are hot too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path
