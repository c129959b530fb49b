"""What keeps the main path honest about its device, checked without one:
`chip_smoke.py` fails where there is no TPU, the compile cache stays where
it was put, replica children get a chip each and their stderr is kept, and
a spec that declares a TPU does not run on a CPU nobody asked for."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from polyaxon_tpu.compiler import compile_operation
from polyaxon_tpu.polyaxonfile import read_polyaxonfile
from polyaxon_tpu.runtime.executor import Executor
from polyaxon_tpu.serving.replicas import SubprocessReplica, replica_chip_env
from polyaxon_tpu.store.local import RunStore
from polyaxon_tpu.utils import jax_platform

REPO = Path(__file__).resolve().parent.parent
SMOKE_FILE = "examples/llama1b_lora_v5e.yaml"
TINY = {"preset": "tiny", "n_layers": 1, "vocab_size": 4096, "seq_len": 64,
        "batch_size": 2, "steps": 1}


# ------------------------------------------------------- (a) the smoke script
@pytest.mark.parametrize("alone", [False, True], ids=["in-checkout", "alone"])
def test_chip_smoke_fails_fast_without_a_tpu(tmp_path, alone):
    """Under JAX_PLATFORMS=cpu with no rehearsal asked for — and in a
    directory that holds nothing else of the repo — the script exits
    non-zero within seconds, `"ok": false` last, and names no device."""
    script = REPO / "chip_smoke.py"
    if alone:
        script = Path(shutil.copy(script, tmp_path))
    proc = subprocess.run(
        [sys.executable, str(script)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=script.parent, capture_output=True, text=True, timeout=30,
    )
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"ok": False, "device": None}


# ------------------------------------------------------ (b) the compile cache
@pytest.fixture()
def config_updates(monkeypatch):
    """Record `jax.config.update` calls instead of applying them: a test
    must not move this process's own compile cache."""
    seen = {}
    monkeypatch.setattr(jax.config, "update", lambda k, v: seen.__setitem__(k, v))
    return seen


def test_cache_dir_from_environment_is_left_alone(monkeypatch, config_updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    monkeypatch.setenv("POLYAXON_HOME", str(tmp_path / "home"))
    assert jax_platform.apply_compilation_cache() == str(tmp_path / "placed")
    assert "jax_compilation_cache_dir" not in config_updates


def test_cache_dir_is_fixed_in_the_checkout(monkeypatch, config_updates, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax_platform.os, "makedirs", lambda *a, **k: None)
    paths = []
    for home in ("home-a", "home-b"):
        monkeypatch.setenv("POLYAXON_HOME", str(tmp_path / home))
        paths.append(jax_platform.apply_compilation_cache())
        assert config_updates.pop("jax_compilation_cache_dir") == paths[-1]
    assert paths[0] == paths[1] == str(REPO / ".jax_compile_cache")
    assert ".jax_compile_cache/" in (REPO / ".gitignore").read_text().split()


def test_cpu_backend_stays_uncached(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert jax_platform.apply_compilation_cache() is None
    assert not config_updates


# ---------------------------------------------------- (c) one process per chip
def test_replica_slots_get_disjoint_chips():
    envs = [replica_chip_env(i, 1, 4) for i in range(4)]
    assert sorted(e["TPU_VISIBLE_CHIPS"] for e in envs) == ["0", "1", "2", "3"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    pairs = [replica_chip_env(i, 2, 4)["TPU_VISIBLE_CHIPS"] for i in range(2)]
    assert pairs == ["0,1", "2,3"]


@pytest.mark.parametrize(
    "replicas,chips", [(5, 1), (3, 2), (2, 4)], ids=["5x1", "3x2", "2x4"]
)
def test_more_replica_chips_than_the_host_has_is_refused(replicas, chips):
    with pytest.raises(ValueError, match="this host has 4"):
        replica_chip_env(replicas - 1, chips, 4)


def test_no_tpu_means_no_chip_assignment():
    assert replica_chip_env(7, 1, None) is None


def test_replica_that_dies_before_ready_surfaces_its_stderr(tmp_path):
    log = tmp_path / "serving" / "replica-0.stderr"
    code = (
        "import os, sys; "
        "sys.stderr.write('chips=' + os.environ['TPU_VISIBLE_CHIPS'] + "
        "' boom: the chip is held by another process'); sys.exit(3)"
    )
    rep = SubprocessReplica(
        lambda port: [sys.executable, "-c", code],
        env=replica_chip_env(2, 1, 4), stderr_path=str(log), ready_timeout_s=30,
    )
    with pytest.raises(RuntimeError) as e:
        rep.start()
    assert "rc=3" in str(e.value)
    assert "chips=2 boom: the chip is held" in str(e.value)
    assert "boom" in log.read_text()


def test_fleet_parent_opens_no_backend():
    """The routing parent must leave the chips to its children: importing
    what it runs initializes no JAX backend."""
    code = (
        "import polyaxon_tpu.cli.main, polyaxon_tpu.serving.replicas, "
        "polyaxon_tpu.serving.router, polyaxon_tpu.scheduler.fleet; "
        "from jax._src import xla_bridge; assert not xla_bridge._backends"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120, cwd=REPO)


# -------------------------------------------- (d) a declared TPU, found absent
def _smoke_run(params=TINY):
    op = read_polyaxonfile(str(REPO / SMOKE_FILE), params=params)
    return compile_operation(op)


def test_declared_tpu_on_unasked_cpu_fails_the_run(tmp_home, monkeypatch):
    monkeypatch.setattr(jax_platform, "_platforms_named", lambda: ["", "", ""])
    compiled = _smoke_run()
    store = RunStore()
    assert Executor(store).execute(compiled) == "failed"
    last = store.get_status(compiled.run_uuid)["conditions"][-1]
    assert last["reason"] == "PlatformMismatchError"
    assert "environment.resources.tpu" in last["message"]
    # refused before anything was built: no trainer, so no device event
    assert not store.read_events(compiled.run_uuid)


@pytest.mark.parametrize("named", ["cpu", "CPU,tpu"])
def test_declared_tpu_on_cpu_that_was_asked_for_is_fine(monkeypatch, named):
    monkeypatch.setattr(jax_platform, "_platforms_named", lambda: ["", named, ""])
    jax_platform.require_declared_tpu(_smoke_run().run, "cpu")


def test_cpu_as_second_choice_was_not_asked_for(monkeypatch):
    """`JAX_PLATFORMS=tpu,cpu` means the TPU: landing on the CPU under it is
    as unasked as under nothing."""
    monkeypatch.setattr(jax_platform, "_platforms_named", lambda: ["", "tpu,cpu", ""])
    with pytest.raises(jax_platform.PlatformMismatchError):
        jax_platform.require_declared_tpu(_smoke_run().run, "cpu")


def test_local_gang_says_where_it_went_and_obeys_the_rule(tmp_home, monkeypatch):
    """N local processes cannot share a chip, so a local gang goes to
    virtual CPU devices — on the record, and not for a TPU spec unasked."""
    monkeypatch.setattr(jax_platform, "_platforms_named", lambda: ["", "", ""])
    compiled = _smoke_run()
    compiled.run.replicas = 2
    store = RunStore()
    assert Executor(store).execute(compiled) == "failed"
    events = {e["kind"]: e for e in store.read_events(compiled.run_uuid)}
    assert events["gang_platform"]["platform"] == "cpu"
    assert events["gang_platform"]["asked"] is False
    last = store.get_status(compiled.run_uuid)["conditions"][-1]
    assert last["reason"] == "PlatformMismatchError"


@pytest.fixture(scope="module")
def served_run(tmp_path_factory):
    """The smoke's file, tiny, trained for a step and checkpointed — once
    for the tests below."""
    store = RunStore(home=tmp_path_factory.mktemp("polyaxon_home"))
    compiled = _smoke_run()
    executor = Executor(store, devices=jax.devices()[:1])
    assert executor.execute(compiled) == "succeeded"
    return store, compiled.run_uuid


def test_trainer_reports_its_device_to_the_run_store(served_run):
    store, uuid = served_run
    events = {e["kind"]: e for e in store.read_events(uuid)}
    dev = events["device"]
    assert dev["platform"] == "cpu" and dev["device_ids"] == [0]
    assert dev["attention_backend"] == "flash" and dev["pallas_interpret"] is True
    assert "device_memory" in events["run_summary"]


def test_server_reports_its_device_and_refuses_an_unasked_cpu(served_run, monkeypatch):
    from polyaxon_tpu.serving import ModelServer

    store, uuid = served_run
    dev = ModelServer.from_run(uuid, store=store).stats()["device"]
    assert dev["platform"] == "cpu" and dev["device_ids"] == [0]
    assert dev["attention_backend"] == "flash" and dev["pallas_interpret"] is True
    assert dev["memory"] == {}  # the CPU backend reports none
    monkeypatch.setattr(jax_platform, "_platforms_named", lambda: ["", "", ""])
    with pytest.raises(jax_platform.PlatformMismatchError):
        ModelServer.from_run(uuid, store=store)


def test_v5e_device_kind_has_a_peak_flops_row():
    from polyaxon_tpu.utils.tpu_info import peak_bf16_flops

    assert peak_bf16_flops("TPU v5 lite") == 197e12
    assert peak_bf16_flops("cpu") is None
