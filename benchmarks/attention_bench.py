"""Attention-backend micro-bench: XLA einsum+softmax vs the Pallas flash
kernel, fwd and fwd+bwd, across sequence lengths on the current device.

Informs the transformer's default `attention:` backend (SURVEY.md §5 long-
context obligation): the XLA path materializes the [B,H,S,S] score matrix
(O(S^2) HBM traffic), the flash kernel streams KV blocks through VMEM
(O(S) memory). The crossover is what this measures on real hardware.

  python benchmarks/attention_bench.py            # default sweep
  python benchmarks/attention_bench.py 1024 8192  # explicit seq lengths

On TPU each seq also runs a grouped-query config (kv_heads = heads/4) —
the flash kernel consumes grouped KV natively via its grid index maps, so
this is the compiled-Mosaic validation of those grids on real hardware.

Prints one JSON line per (seq, kv_heads, backend, mode) with tokens/sec
and ms/call; schema pinned by tests/test_benchmarks.py.
"""

from __future__ import annotations

import argparse
import json
import sys
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


from _timing import time_call as _time_call  # noqa: E402 — shared methodology


def _already_captured(out_path: Path) -> set:
    """(seq, kv_heads, backend, mode) rows already landed in --out —
    a resumed sweep (the earlier run was killed) skips them instead of
    duplicating lines. Error rows don't count: they get retried."""
    done = set()
    if not out_path.exists():
        return done
    for line in out_path.read_text().splitlines():
        try:
            r = json.loads(line)
        except ValueError:
            continue
        if "mode" in r and "error" not in r:
            done.add((r["seq"], r["kv_heads"], r["backend"], r["mode"]))
    return done


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("seqs", nargs="*", type=int, help="explicit seq lengths")
    ap.add_argument(
        "--out", default=None,
        help="ALSO append each result line to this file as it is produced "
             "— point it at the final committed .jsonl, not a temp file, "
             "so a run killed mid-sweep still "
             "leaves every completed measurement on disk where the "
             "evidence commit finds it; a re-run resumes past them",
    )
    args = ap.parse_args()
    # POLYAXON_JAX_PLATFORM / POLYAXON_NUM_CPU_DEVICES apply through
    # jax.config, so before the backend initializes
    from polyaxon_tpu.utils.jax_platform import apply_platform_env

    apply_platform_env()

    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.ops.attention import dot_product_attention

    sink = None
    done = set()
    if args.out:
        out_path = Path(args.out)
        done = _already_captured(out_path)
        out_path.parent.mkdir(parents=True, exist_ok=True)
        # line-buffered append: each completed measurement hits the disk
        # before the next one starts
        sink = open(out_path, "a", buffering=1)

    def emit(rec: dict):
        line = json.dumps(rec)
        print(line, flush=True)
        if sink is not None:
            sink.write(line + "\n")

    seqs = args.seqs or [512, 1024, 2048, 4096, 8192]
    device = jax.devices()[0]
    batch, heads, head_dim = 4, 16, 128
    on_tpu = device.platform == "tpu"
    backends = ("xla", "flash")
    kv_sweep = (heads, heads // 4)
    if not on_tpu:
        # CPU runs the Pallas kernel in interpret mode (minutes per call) —
        # the backend comparison is only meaningful on the chip anyway
        seqs = [s for s in seqs if s <= 512]
        batch, backends, kv_sweep = 2, ("xla",), (heads,)

    for seq in seqs:
      for kv_heads in kv_sweep:
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(
            key, (batch, seq, heads, head_dim), jnp.bfloat16
        )
        k, v = (
            jax.random.normal(
                jax.random.fold_in(key, i),
                (batch, seq, kv_heads, head_dim),
                jnp.bfloat16,
            )
            for i in (1, 2)
        )
        for backend in backends:
            try:
                fwd = jax.jit(
                    partial(
                        dot_product_attention, causal=True, backend=backend
                    )
                )

                def loss(q, k, v):
                    return (
                        dot_product_attention(
                            q, k, v, causal=True, backend=backend
                        )
                        .astype(jnp.float32)
                        .sum()
                    )

                bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                for mode, fn in (("fwd", fwd), ("fwd+bwd", bwd)):
                    if (seq, kv_heads, backend, mode) in done:
                        continue  # resumed sweep: already on disk
                    dt = _time_call(fn, q, k, v)
                    emit(
                        {
                            "seq": seq,
                            "backend": backend,
                            "mode": mode,
                            "ms_per_call": round(dt * 1e3, 3),
                            "tokens_per_sec": round(batch * seq / dt, 1),
                            "platform": device.platform,
                            "device_kind": device.device_kind,
                            "batch": batch,
                            "heads": heads,
                            "kv_heads": kv_heads,
                            "head_dim": head_dim,
                        }
                    )
            except Exception as e:  # noqa: BLE001 — report, keep sweeping
                emit(
                    {
                        "seq": seq,
                        "kv_heads": kv_heads,
                        "backend": backend,
                        "error": f"{type(e).__name__}: {e}"[:200],
                    }
                )


if __name__ == "__main__":
    main()
