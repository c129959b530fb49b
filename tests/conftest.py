"""Test harness: fake an 8-device TPU slice on CPU so sharding/collective
tests run without hardware (SURVEY.md §4: the reference tests multi-node by
golden-rendering specs; we additionally execute on a virtual mesh).

The platform and the virtual device count go through jax.config before any
backend initializes — conftest import time is early enough.
"""

import os

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# children spawned by tests (multi-process distributed harness) inherit these
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_NUM_CPU_DEVICES"] = "8"

import faulthandler  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402

# Per-test wall-clock guard (pytest-timeout isn't in the image): a hung
# collective/rendezvous must fail the one test, not the whole suite run.
# Two layers: SIGALRM raises a clean TimeoutError for Python-level hangs;
# a watchdog thread hard-exits for native hangs (a blocked XLA rendezvous
# never returns to the bytecode loop, so a Python signal handler can't fire)
# after dumping all thread stacks.
_TEST_TIMEOUT_S = int(os.environ.get("POLYAXON_TEST_TIMEOUT", "420"))


@pytest.fixture(autouse=True)
def _test_alarm():
    done = threading.Event()

    def _watchdog():
        if not done.wait(_TEST_TIMEOUT_S + 60):
            sys.stderr.write(
                f"\n=== test watchdog: native hang > {_TEST_TIMEOUT_S + 60}s, "
                "dumping stacks and exiting ===\n"
            )
            faulthandler.dump_traceback()
            os._exit(70)

    watchdog = threading.Thread(target=_watchdog, daemon=True)
    watchdog.start()

    if not hasattr(signal, "SIGALRM"):  # non-POSIX fallback: watchdog only
        yield
        done.set()
        return

    def _on_alarm(signum, frame):
        raise TimeoutError(f"test exceeded {_TEST_TIMEOUT_S}s wall-clock guard")

    old = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(_TEST_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
        done.set()


@pytest.fixture()
def tmp_home(tmp_path, monkeypatch):
    """Isolated POLYAXON_HOME so tests never touch the real run store."""
    home = tmp_path / "polyaxon_home"
    monkeypatch.setenv("POLYAXON_HOME", str(home))
    return home
