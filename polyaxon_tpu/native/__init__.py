"""Native components (C++): the gang launcher/supervisor.

Parity slot for the reference's Go operator (SURVEY.md §2 native census).
`launcher_path()` returns the binary, built by the in-tree
Makefile (g++ is in the base image; no pip deps).
"""

from __future__ import annotations

import subprocess
from pathlib import Path

_DIR = Path(__file__).resolve().parent
_BINARY = _DIR / "polyaxon-launcher"


class NativeBuildError(RuntimeError):
    pass


def launcher_path() -> str:
    """Path to the compiled launcher. Always goes through make: its
    launcher.cpp dependency makes this a no-op when the binary is fresh and
    a rebuild when the source changed — building only when the binary is
    missing would let a stale one win over changed source."""
    proc = subprocess.run(
        ["make", "-C", str(_DIR), _BINARY.name],
        capture_output=True,
        text=True,
    )
    if proc.returncode != 0 or not _BINARY.exists():
        raise NativeBuildError(
            f"building polyaxon-launcher failed:\n{proc.stdout}\n{proc.stderr}"
        )
    return str(_BINARY)


def free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def pick_port(seed: str, base: int = 23000, span: int = 20000) -> int:
    """Deterministic-per-run coordinator port with probing.

    free_port() releases the port before the gang binds it, so two
    concurrent trials could be handed the same one; hashing the run uuid
    spreads concurrent gangs apart, and probing skips ports that happen to
    be taken right now."""
    import hashlib
    import socket

    start = base + int(hashlib.sha1(seed.encode()).hexdigest(), 16) % span
    for i in range(64):
        port = base + (start - base + i) % span
        with socket.socket() as s:
            try:
                s.bind(("127.0.0.1", port))
            except OSError:
                continue
            return port
    raise RuntimeError("no free coordinator port found")
