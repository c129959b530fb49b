"""End-to-end slice tests: trainer, executor, store, tracking, CLI.

Multi-device behavior runs on the virtual 8-device CPU mesh from conftest
(SURVEY.md §4: execute on a fake slice, not just golden-render)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.compiler import compile_operation
from polyaxon_tpu.polyaxonfile import read_polyaxonfile
from polyaxon_tpu.runtime import Executor
from polyaxon_tpu.runtime.trainer import Trainer
from polyaxon_tpu.schemas.run_kinds import V1Program
from polyaxon_tpu.store import RunStore


def make_program(**train_overrides):
    train = {"steps": 10, "logEvery": 5, "precision": "float32", "seed": 0}
    train.update(train_overrides)
    return V1Program.model_validate(
        {
            "model": {"name": "mlp", "config": {"hidden": [32], "input_dim": 16, "num_classes": 4}},
            "data": {"name": "synthetic", "batchSize": 32, "config": {"shape": [16], "num_classes": 4}},
            "optimizer": {"name": "adamw", "learningRate": 0.01},
            "train": train,
        }
    )


class TestTrainer:
    def test_loss_descends_single_device(self):
        logs = []
        t = Trainer(make_program(steps=30), mesh_axes={"data": 1},
                    devices=jax.devices()[:1], log_fn=lambda s, m: logs.append((s, m)))
        result = t.run()
        assert result.history[0]["loss"] > result.history[-1]["loss"]
        assert logs and logs[-1][0] == 30

    def test_dp_over_8_devices_matches_single_device(self):
        """Same seed → same loss trajectory whether batch is sharded 8-way
        or runs on one device: the SPMD step is numerically the program."""
        r1 = Trainer(make_program(), mesh_axes={"data": 8}).run()
        r2 = Trainer(make_program(), mesh_axes={"data": 1}, devices=jax.devices()[:1]).run()
        np.testing.assert_allclose(
            [h["loss"] for h in r1.history],
            [h["loss"] for h in r2.history],
            rtol=2e-4,
        )

    def test_fsdp_and_model_axes(self):
        r = Trainer(make_program(), mesh_axes={"data": 2, "fsdp": 2, "model": 2}).run()
        assert r.history[-1]["loss"] < r.history[0]["loss"]
        # params actually sharded over fsdp/model axes
        t = Trainer(make_program(steps=1), mesh_axes={"data": 2, "fsdp": 2, "model": 2})
        kernel = t.state.params["dense_0"]["kernel"]
        assert len(kernel.sharding.device_set) > 1

    def test_mixed_precision_bf16(self):
        r = Trainer(make_program(precision="mixed", steps=10), mesh_axes={"data": 8}).run()
        assert r.history[-1]["loss"] < r.history[0]["loss"]

    def test_checkpoint_retention_keep(self, tmp_path):
        """checkpointKeep bounds on-disk checkpoints: a frequent-save run
        must not fill the artifact store."""
        import re

        from polyaxon_tpu.runtime.checkpoint import close_all

        ckdir = tmp_path / "ck-keep"
        p = make_program(steps=8, checkpointEvery=2, checkpointKeep=2)
        t = Trainer(p, mesh_axes={"data": 8}, checkpoint_dir=str(ckdir))
        t.run()
        close_all()  # flush async saves + release the manager
        steps = sorted(
            int(d.name) for d in ckdir.iterdir() if re.fullmatch(r"\d+", d.name)
        )
        assert steps == [6, 8], steps  # only the newest `keep` survive

    def test_checkpoint_keep_survives_resume(self, tmp_path):
        """Resume touches the manager before the first save; checkpointKeep
        must flow through restore or the cached manager pins the default
        retention and silently overrides the spec."""
        import re

        from polyaxon_tpu.runtime.checkpoint import close_all

        ckdir = tmp_path / "ck-resume-keep"
        p = make_program(steps=4, checkpointEvery=2, checkpointKeep=4)
        Trainer(p, mesh_axes={"data": 8}, checkpoint_dir=str(ckdir)).run()
        close_all()
        p2 = make_program(steps=10, checkpointEvery=2, checkpointKeep=4, resume=True)
        t2 = Trainer(p2, mesh_axes={"data": 8}, checkpoint_dir=str(ckdir))
        assert t2.restore() == 4  # manager first touched by resume
        t2.run()
        close_all()
        steps = sorted(
            int(d.name) for d in ckdir.iterdir() if re.fullmatch(r"\d+", d.name)
        )
        assert steps == [4, 6, 8, 10], steps  # keep=4 honored, not default 3

    def test_checkpoint_resume(self, tmp_path):
        ckdir = str(tmp_path / "ck")
        p = make_program(steps=10, checkpointEvery=5)
        t1 = Trainer(p, mesh_axes={"data": 8}, checkpoint_dir=ckdir)
        t1.run()
        p2 = make_program(steps=15, checkpointEvery=5, resume=True)
        t2 = Trainer(p2, mesh_axes={"data": 8}, checkpoint_dir=ckdir)
        start = t2.restore()
        assert start == 10
        assert int(t2.state.step) == 10


class TestExecutorAndStore:
    def test_mnist_yaml_end_to_end(self, tmp_home):
        op = read_polyaxonfile("examples/mnist.yaml", params={"steps": 6, "batch_size": 32})
        store = RunStore()
        compiled = compile_operation(op)
        status = Executor(store, devices=jax.devices()[:1]).execute(compiled)
        assert status == "succeeded"
        metrics = store.read_metrics(compiled.run_uuid)
        assert metrics and metrics[-1]["step"] == 6
        statuses = [c["type"] for c in store.get_status(compiled.run_uuid)["conditions"]]
        assert statuses == [
            "created", "compiled", "queued", "scheduled", "starting", "running", "succeeded",
        ]

    def test_failed_run_records_reason(self, tmp_home):
        op = read_polyaxonfile("examples/mnist.yaml")
        # unknown model name → compile passes (registry checked at runtime), run fails
        op.component.run.program.model.name = "no-such-model"
        compiled = compile_operation(op)
        status = Executor(RunStore()).execute(compiled)
        assert status == "failed"
        st = RunStore().get_status(compiled.run_uuid)
        assert "no-such-model" in st["conditions"][-1]["message"]

    def test_container_job_subprocess(self, tmp_home):
        from polyaxon_tpu.schemas import V1Operation

        op = V1Operation.model_validate(
            {
                "kind": "operation",
                "name": "echo",
                "component": {
                    "kind": "component",
                    "run": {"kind": "job", "container": {"command": ["echo", "hello-{{ globals.uuid }}"]}},
                },
            }
        )
        store = RunStore()
        compiled = compile_operation(op)
        assert Executor(store).execute(compiled) == "succeeded"
        assert f"hello-{compiled.run_uuid}" in store.read_logs(compiled.run_uuid)

    def test_retry_on_failure(self, tmp_home):
        from polyaxon_tpu.schemas import V1Operation

        op = V1Operation.model_validate(
            {
                "kind": "operation",
                "name": "flaky",
                "component": {
                    "kind": "component",
                    "termination": {"maxRetries": 2},
                    "run": {"kind": "job", "container": {"command": ["false"]}},
                },
            }
        )
        store = RunStore()
        compiled = compile_operation(op)
        assert Executor(store).execute(compiled) == "failed"
        types = [c["type"] for c in store.get_status(compiled.run_uuid)["conditions"]]
        assert types.count("retrying") == 2


class TestTracking:
    def test_standalone_tracked_run(self, tmp_home):
        from polyaxon_tpu import tracking

        run = tracking.Run(name="nb", project="p1")
        run.log_metrics(step=1, loss=0.5)
        run.log_metrics(step=2, loss=0.25)
        run.log_outputs(best_loss=0.25)
        run.end()
        store = RunStore()
        assert store.get_status(run.uuid)["status"] == "succeeded"
        assert [m["loss"] for m in store.read_metrics(run.uuid)] == [0.5, 0.25]
        events = store.read_events(run.uuid)
        assert events[0]["outputs"] == {"best_loss": 0.25}

    def test_attach_via_env(self, tmp_home, monkeypatch):
        from polyaxon_tpu import tracking

        store = RunStore()
        store.create_run("abc123", "r", "p", {})
        monkeypatch.setenv("POLYAXON_RUN_UUID", "abc123")
        run = tracking.Run()
        run.log_metric("m", 1.0, step=0)
        assert store.read_metrics("abc123")[0]["m"] == 1.0


class TestCli:
    @pytest.mark.parametrize(
        "example,batch",
        [("mnist", 16), ("granite_hybrid_lora", 8), ("ling_hybrid_lora", 8)],  # 8: a row a virtual device
    )
    def test_run_and_ops(self, tmp_home, example, batch):
        from click.testing import CliRunner

        from polyaxon_tpu.cli.main import cli

        runner = CliRunner()
        res = runner.invoke(
            cli, ["run", "-f", f"examples/{example}.yaml", "-P", "steps=4",
                  "-P", f"batch_size={batch}"]
        )
        assert res.exit_code == 0, res.output
        res = runner.invoke(cli, ["ops", "ls"])
        assert "succeeded" in res.output
        uid = res.output.split()[0]
        res = runner.invoke(cli, ["ops", "metrics", "-uid", uid])
        assert json.loads(res.output.splitlines()[-1])["step"] == 4
        res = runner.invoke(cli, ["ops", "statuses", "-uid", uid])
        assert "succeeded" in res.output

    def test_check(self, tmp_home):
        from click.testing import CliRunner

        from polyaxon_tpu.cli.main import cli

        res = CliRunner().invoke(cli, ["check", "-f", "examples/resnet50.yaml"])
        assert res.exit_code == 0, res.output
        spec = json.loads(res.output)
        # mesh -1 resolved against the 2x4 tpu slice
        assert spec["component"]["run"]["mesh"] == {"data": 8}

    @pytest.mark.slow
    def test_ops_compare(self, tmp_home):
        from click.testing import CliRunner

        from polyaxon_tpu.cli.main import cli

        runner = CliRunner()
        uids = []
        for lr in ("0.001", "0.01"):
            res = runner.invoke(
                cli,
                ["run", "-f", "examples/mnist.yaml", "-P", "steps=3",
                 "-P", "batch_size=16", "-P", f"lr={lr}"],
            )
            assert res.exit_code == 0, res.output
            uids.append(res.output.split("run ")[1][:8])
        res = runner.invoke(
            cli, ["ops", "compare", "--uid", uids[0], "--uid", uids[1]]
        )
        assert res.exit_code == 0, res.output
        assert "param.lr" in res.output and "loss" in res.output
        assert "0.001" in res.output and "0.01" in res.output
        res = runner.invoke(cli, ["ops", "compare", "--uid", uids[0]])
        assert res.exit_code != 0 and "at least two" in res.output


def test_grad_accum_matches_full_batch(tmp_home):
    """gradAccum=4 over a batch of 32 must take the same first optimizer
    step as one full-batch update (same data, float32, SGD) — accumulation
    is exact, not approximate."""
    import jax
    import numpy as np

    from polyaxon_tpu.runtime.trainer import Trainer
    from polyaxon_tpu.schemas.run_kinds import (
        V1DataSpec,
        V1ModelSpec,
        V1OptimizerSpec,
        V1Program,
        V1TrainSpec,
    )

    def prog(accum):
        return V1Program(
            model=V1ModelSpec(
                name="mlp", config={"input_dim": 8, "num_classes": 2, "hidden": [4]}
            ),
            data=V1DataSpec(
                name="synthetic", batch_size=32,
                config={"shape": [8], "num_classes": 2},
            ),
            optimizer=V1OptimizerSpec(name="sgd", learning_rate=0.1),
            train=V1TrainSpec(
                steps=1, log_every=1, precision="float32", seed=3,
                grad_accum=accum, donate_state=False,
            ),
        )

    dev = [jax.devices()[0]]
    t_full = Trainer(prog(None), devices=dev)
    t_acc = Trainer(prog(4), devices=dev)
    r_full = t_full.run()
    r_acc = t_acc.run()
    # same seed → same data stream → identical first-step loss and params
    assert abs(r_full.history[0]["loss"] - r_acc.history[0]["loss"]) < 1e-5
    for a, b in zip(
        jax.tree.leaves(t_full.state.params), jax.tree.leaves(t_acc.state.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_grad_accum_trains_on_mesh(tmp_home):
    from polyaxon_tpu.runtime.trainer import Trainer
    from polyaxon_tpu.schemas.run_kinds import (
        V1DataSpec,
        V1ModelSpec,
        V1OptimizerSpec,
        V1Program,
        V1TrainSpec,
    )

    program = V1Program(
        model=V1ModelSpec(
            name="mlp", config={"input_dim": 16, "num_classes": 4, "hidden": [8]}
        ),
        data=V1DataSpec(
            name="synthetic", batch_size=32, config={"shape": [16], "num_classes": 4}
        ),
        optimizer=V1OptimizerSpec(name="adamw", learning_rate=0.01),
        train=V1TrainSpec(steps=20, log_every=20, precision="float32", grad_accum=2),
    )
    result = Trainer(program, mesh_axes={"data": -1}).run()
    first, last = result.history[0], result.history[-1]
    assert last["loss"] == last["loss"]  # finite
    assert last["loss"] < 1.6  # descending on the learnable stream


@pytest.mark.slow
@pytest.mark.parametrize("policy", ["nothing", "dots", "dots_no_batch"])
def test_remat_policies_compile_and_train(tmp_home, policy):
    from polyaxon_tpu.runtime.trainer import Trainer
    from polyaxon_tpu.schemas.run_kinds import (
        V1DataSpec,
        V1ModelSpec,
        V1OptimizerSpec,
        V1Program,
        V1TrainSpec,
    )

    program = V1Program(
        model=V1ModelSpec(
            name="transformer_lm", config={"preset": "tiny", "seq_len": 32}
        ),
        data=V1DataSpec(
            name="synthetic_text", batch_size=8,
            config={"seq_len": 32, "vocab_size": 4096},
        ),
        optimizer=V1OptimizerSpec(name="adamw", learning_rate=1e-3),
        train=V1TrainSpec(
            steps=2, log_every=2, precision="float32", remat_policy=policy
        ),
    )
    result = Trainer(program, mesh_axes={"data": -1}).run()
    assert result.history[-1]["loss"] == result.history[-1]["loss"]


def test_service_runs_until_stopped(tmp_home, tmp_path):
    """Services stay RUNNING until a stop lands (then STOPPED, process
    terminated); self-exit is a failure, not success."""
    import threading
    import time

    import yaml

    from polyaxon_tpu.client import RunClient
    from polyaxon_tpu.schemas.lifecycle import V1Statuses

    def svc_op(cmd):
        spec = {
            "version": 1.1,
            "kind": "operation",
            "name": "svc",
            "component": {
                "kind": "component",
                "name": "svc",
                "run": {
                    "kind": "service",
                    "ports": [7777],
                    "container": {"command": ["sh", "-c", cmd]},
                },
            },
        }
        p = tmp_path / "svc.yaml"
        p.write_text(yaml.safe_dump(spec))
        from polyaxon_tpu.polyaxonfile import read_polyaxonfile

        return read_polyaxonfile(str(p))

    client = RunClient()
    results = {}
    op = svc_op('echo "serving on $POLYAXON_SERVICE_PORT"; sleep 60')

    def _run():
        results["uuid"] = client.create(op, queue=False)

    t = threading.Thread(target=_run)
    t.start()
    deadline = time.time() + 30
    uuid = None
    while time.time() < deadline:
        runs = client.list()
        if runs and runs[0]["status"] == V1Statuses.RUNNING:
            uuid = runs[0]["uuid"]
            break
        time.sleep(0.2)
    assert uuid, "service never reached RUNNING"
    time.sleep(1.0)
    client.stop(uuid)
    t.join(timeout=30)
    assert not t.is_alive()
    assert client.get(uuid)["status"] == V1Statuses.STOPPED
    assert "serving on 7777" in client.logs(uuid)

    # a service that exits by itself FAILED, even with exit code 0
    uuid2 = client.create(svc_op("true"), queue=False)
    assert client.get(uuid2)["status"] == V1Statuses.FAILED
    assert "exited unexpectedly" in client.logs(uuid2)


def test_mesh_model_axis_mismatch_friendly_error(tmp_home):
    """A model axis that doesn't divide n_heads fails with a config error,
    not an opaque XLA sharding crash."""
    from polyaxon_tpu.runtime.trainer import Trainer
    from polyaxon_tpu.schemas.run_kinds import (
        V1DataSpec,
        V1ModelSpec,
        V1Program,
    )

    program = V1Program(
        model=V1ModelSpec(
            name="transformer_lm",
            config={"dim": 96, "n_layers": 2, "n_heads": 3, "n_kv_heads": 3,
                    "vocab_size": 256, "seq_len": 32},
        ),
        data=V1DataSpec(
            name="synthetic_text", batch_size=8,
            config={"seq_len": 32, "vocab_size": 256},
        ),
    )
    with pytest.raises(ValueError, match="n_heads .3. is not divisible"):
        Trainer(program, mesh_axes={"model": 2, "data": 4})

    with pytest.raises(ValueError, match="no\\s+.?experts"):
        Trainer(program, mesh_axes={"expert": 2, "data": 4})


def test_data_model_shape_mismatch_is_clear():
    """A dataset whose feature shape disagrees with the model must fail at
    build time with a config-level message, not a flax scope error deep in
    the first apply."""
    import pytest

    from polyaxon_tpu.runtime.trainer import Trainer

    p = make_program()
    p.model.config = {"input_dim": 16, "num_classes": 4, "hidden": [32]}
    p.data.config = {"shape": [32], "num_classes": 4}
    with pytest.raises(ValueError, match="data/model shape mismatch"):
        Trainer(p, mesh_axes={"data": 8})

    # flattening models compare by element count, not tuple equality:
    # (28,28,1) into an mlp expecting (784,) is a valid, working config
    p2 = make_program(steps=1, logEvery=1)
    p2.model.config = {"input_dim": 784, "num_classes": 10, "hidden": [16]}
    p2.data = p2.data.model_copy(update={"name": "mnist", "config": {"flat": False}})
    Trainer(p2, mesh_axes={"data": 8})  # must not raise


# --------------------------------------------------------------------------
# A LoRA step differentiates its adapters only (PR 28). Widths chosen so that
# tokens (2 x 24 = 48), dim (64), hidden_dim (96), the KV width (2 x 16 = 32),
# the vocabulary (160) and the rank (4) all differ: a product's output shape
# then says whose weight gradient it is.


def lora_program(lora=True, rows=2, model=None, **train_overrides):
    cfg = {
        "dim": 64, "n_layers": 2, "n_heads": 4, "n_kv_heads": 2,
        "hidden_dim": 96, "vocab_size": 160, "seq_len": 24,
        "fused_lm_loss": True, **(model or {}),
    }
    if lora:
        cfg["lora"] = {
            "rank": 4, "alpha": 8,
            "targets": ["q_proj", "k_proj", "v_proj", "o_proj"],
        }
    train = {"steps": 3, "logEvery": 1, "precision": "float32", "seed": 0}
    train.update(train_overrides)
    return V1Program.model_validate(
        {
            "model": {"name": "transformer_lm", "config": cfg},
            "data": {
                "name": "synthetic_text", "batchSize": rows,
                "config": {"seq_len": 24, "vocab_size": 160},
            },
            "optimizer": {"name": "adamw", "learningRate": 0.01},
            "train": train,
        }
    )


def _lora_trainer(**kw):
    events = kw.pop("events", None)
    return Trainer(
        lora_program(**kw),
        devices=jax.devices()[:1],
        event_fn=(lambda kind, body: events.append((kind, body)))
        if events is not None
        else None,
    )


def _batches(trainer, n):
    it = iter(trainer.data.iterator)
    return [jax.device_put(next(it), trainer.b_shard) for _ in range(n)]


def _leaves_by_path(tree):
    from polyaxon_tpu.parallel.sharding import _path_str

    return {
        _path_str(p): np.asarray(x)
        for p, x in jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]
    }


def _is_adapter(path):
    return path.endswith(("lora_a", "lora_b"))


def _run_steps(trainer, batches):
    metrics = []
    for b in batches:
        trainer.state, m = trainer.train_step(trainer.state, b)
        metrics.append(jax.device_get(m))
    return metrics


def _product_shapes(trainer, batch, step=None):
    """Output shapes of every dot and convolution of the compiled step (the
    Trainer's own, or `step`: one rung's)."""
    import re

    step = step or trainer.train_step
    text = step.lower(trainer.state, batch).compile().as_text()
    return {
        tuple(int(d) for d in dims.split(","))
        for dims, _ in re.findall(
            r"= \w+\[([\d,]+)\]\S* (dot|convolution)\(", text
        )
    }


def _gradient_of_all(trainer):
    """The step as it was built before PR 28: the loss differentiated with
    respect to every leaf, the frozen leaves' gradients handed to the
    optimizer's `set_to_zero`. Returns (params, opt_state, grads)."""
    import optax

    bundle = trainer.bundle

    def step(params, opt_state, batch):
        def loss_of(p):
            features = bundle.module.apply(
                {"params": p}, batch["inputs"], train=True, return_features=True,
                rngs={"dropout": jax.random.PRNGKey(0)},  # rate 0: not drawn
            )
            return bundle.fused_loss(p, features, batch)

        grads = jax.grad(loss_of)(params)
        updates, opt_state = trainer.tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, grads

    return jax.jit(step)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_lora_step_has_no_product_shaped_like_a_frozen_kernel(remat):
    t = _lora_trainer(remat=remat)
    leaves = _leaves_by_path(t.state.params)
    frozen = {
        shape
        for path, x in leaves.items()
        if not _is_adapter(path) and x.ndim == 2
        for shape in (x.shape, x.shape[::-1])
    }
    adapters = {x.shape for path, x in leaves.items() if _is_adapter(path)}
    assert {(64, 64), (64, 32), (64, 96), (96, 64), (64, 160)} <= frozen
    assert adapters == {(64, 4), (4, 64), (4, 32)}
    products = _product_shapes(t, _batches(t, 1)[0])
    assert not products & frozen, sorted(products & frozen)
    assert adapters <= products  # the adapters' own weight gradients stay


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_lora_steps_leave_frozen_leaves_bit_equal(remat):
    t = _lora_trainer(remat=remat)
    start = _leaves_by_path(t.state.params)
    _run_steps(t, _batches(t, 3))
    end = _leaves_by_path(t.state.params)
    assert any(_is_adapter(p) for p in start)
    for path, before in start.items():
        if not _is_adapter(path):
            np.testing.assert_array_equal(end[path], before, err_msg=path)
        elif path.endswith("lora_b"):
            assert np.abs(end[path] - before).max() > 0, path


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_lora_adapters_match_a_step_that_differentiates_every_leaf(remat):
    t = _lora_trainer(remat=remat)
    batches = _batches(t, 3)
    old_step = _gradient_of_all(t)
    params, opt_state = t.state.params, t.state.opt_state
    for b in batches:
        params, opt_state, _ = old_step(params, opt_state, b)
    want = _leaves_by_path(params)
    _run_steps(t, batches)
    got = _leaves_by_path(t.state.params)
    for path in want:
        if _is_adapter(path):
            np.testing.assert_allclose(
                got[path], want[path], rtol=1e-4, atol=1e-6, err_msg=path
            )
    # the moments sit where a checkpoint written before PR 28 has them
    assert list(_leaves_by_path(t.state.opt_state)) == list(_leaves_by_path(opt_state))


def test_lora_grad_norm_is_the_adapters_norm():
    import optax

    events = []
    t = _lora_trainer(events=events)
    (batch,) = _batches(t, 1)
    _, _, grads = _gradient_of_all(t)(t.state.params, t.state.opt_state, batch)
    grads = _leaves_by_path(grads)
    adapters = {p: g for p, g in grads.items() if _is_adapter(p)}
    (metrics,) = _run_steps(t, [batch])
    np.testing.assert_allclose(
        metrics["grad_norm"], optax.global_norm(adapters), rtol=1e-5
    )
    assert metrics["grad_norm"] < 0.9 * optax.global_norm(grads)
    # and the run's log says how many parameters the step differentiates
    sizes = {p: x.size for p, x in _leaves_by_path(t.state.params).items()}
    n_train = sum(n for p, n in sizes.items() if _is_adapter(p))
    assert ("differentiated", {
        "trainable_params": n_train,
        "frozen_params": sum(sizes.values()) - n_train,
        "frozen_dtype": "float32",
        "frozen_compute_type_bytes": 0,
    }) in events
    snap = t.telemetry.snapshot()
    assert snap["train.params_differentiated"] == n_train
    assert snap["train.params_frozen"] == sum(sizes.values()) - n_train


def test_full_training_differentiates_every_leaf():
    """No `trainable_patterns`: the branch the step took before PR 28."""
    import optax

    events = []
    t = _lora_trainer(lora=False, events=events)
    assert t._train_labels is None
    (batch,) = _batches(t, 1)
    start = _leaves_by_path(t.state.params)
    _, _, grads = _gradient_of_all(t)(t.state.params, t.state.opt_state, batch)
    # a kernel's weight gradient is a product of the compiled step here
    assert {(64, 64), (64, 96)} & _product_shapes(t, batch)
    (metrics,) = _run_steps(t, [batch])
    np.testing.assert_allclose(
        metrics["grad_norm"], optax.global_norm(_leaves_by_path(grads)), rtol=1e-5
    )
    end = _leaves_by_path(t.state.params)
    for path, before in start.items():
        assert np.abs(end[path] - before).max() > 0, path
    moments = {
        p: m for p, m in _leaves_by_path(t.state.opt_state).items() if "mu/" in p
    }
    assert len(moments) == len(start)
    for path, m in moments.items():
        assert np.abs(m).max() > 0, path
    n = sum(x.size for x in start.values())
    assert ("differentiated", {
        "trainable_params": n, "frozen_params": 0, "frozen_dtype": None,
        "frozen_compute_type_bytes": 0,
    }) in events


def test_lora_grad_accum_matches_the_doubled_batch():
    whole = _lora_trainer(rows=4)
    halves = _lora_trainer(rows=4, gradAccum=2)
    assert halves.grad_accum == 2 and whole.grad_accum == 1
    batches = _batches(whole, 3)
    m_whole = _run_steps(whole, batches)
    m_halves = _run_steps(halves, batches)
    np.testing.assert_allclose(
        [m["loss"] for m in m_halves], [m["loss"] for m in m_whole], rtol=1e-5
    )
    np.testing.assert_allclose(
        m_halves[0]["grad_norm"], m_whole[0]["grad_norm"], rtol=1e-4
    )
    want, got = _leaves_by_path(whole.state.params), _leaves_by_path(halves.state.params)
    for path in want:
        if _is_adapter(path):
            np.testing.assert_allclose(
                got[path], want[path], rtol=1e-4, atol=1e-6, err_msg=path
            )
        else:
            np.testing.assert_array_equal(got[path], want[path], err_msg=path)
    # the accumulated gradient holds adapters only: no buffer of a frozen
    # kernel's shape is carried through the microbatch loop
    assert not _product_shapes(halves, batches[0]) & {(64, 64), (64, 96), (96, 64)}


# A leaf the optimizer never updates is stored in the type the step reads it
# in: under `precision: mixed` a LoRA step's frozen half is bf16, cast once
# in the init program, where the step cast f32 masters on every call before.


def _converted_arguments(trainer, batch):
    """Shapes of the step's arguments that the lowered step converts to
    another float type, as (shape, from, to)."""
    import re

    text = trainer.train_step.lower(trainer.state, batch).as_text()
    return {
        (tuple(int(d) for d in dims.split("x")[:-1]), src, dst)
        for dims, src, dst in re.findall(
            r"stablehlo.convert %arg\d+ : \(tensor<((?:\d+x)+)(\w+)>\) -> tensor<[\dx]+(\w+)>",
            text,
        )
    }


def _masters_in_f32(trainer):
    """The params as the init recipe made them before the frozen half was
    stored in the compute type: every float leaf in the masters' type."""
    from polyaxon_tpu.runtime.trainer import make_param_init

    bundle = trainer.bundle
    init_fn = make_param_init(
        bundle, trainer.param_dtype, bundle.example_inputs(trainer.data.batch_size)
    )
    params, _ = jax.jit(init_fn)(jax.random.PRNGKey(int(trainer.tspec.seed)))
    return params


def _stores_its_frozen_half_in_the_type_it_reads(tmp_path, lora, precision, frozen_type):
    events = []
    t = _lora_trainer(lora=lora, precision=precision, events=events)
    stored = _leaves_by_path(t.state.params)
    before = _leaves_by_path(_masters_in_f32(t))
    assert list(stored) == list(before)
    moved = 0
    for path, x in stored.items():
        if lora and precision == "mixed" and not _is_adapter(path):
            assert x.dtype == np.dtype(jnp.bfloat16), path
            assert before[path].dtype == np.float32, path
            # the same values the step read before: the masters' cast
            np.testing.assert_array_equal(
                x, before[path].astype(jnp.bfloat16), err_msg=path
            )
            moved += x.nbytes
        else:  # adapters, a full fine-tune, float32 or bfloat16: as before
            assert x.dtype == before[path].dtype, path
            np.testing.assert_array_equal(x, before[path], err_msg=path)
    assert (moved > 0) == (lora and precision == "mixed")
    (body,) = [b for kind, b in events if kind == "differentiated"]
    assert body["frozen_dtype"] == frozen_type
    assert body["frozen_compute_type_bytes"] == moved
    assert t.telemetry.snapshot()["train.params_frozen_compute_type_bytes"] == moved
    # the lowered step converts no frozen kernel: what is stored is what it reads
    converted = _converted_arguments(t, _batches(t, 1)[0])
    kernels = {x.shape for p, x in stored.items() if x.ndim == 2 and not _is_adapter(p)}
    frozen_converted = {c for c in converted if c[0] in kernels}
    if precision == "mixed" and not lora:
        assert frozen_converted  # the control: trained masters are cast
    else:
        assert not frozen_converted, sorted(frozen_converted)


def _reads_the_operands_f32_masters_gave(tmp_path, remat):
    """One step on the stored bf16 frozen half against the same step on the
    f32 masters the init made before, cast inside the step: the same loss,
    gradient norm, first moments and adapters, bit for bit."""
    t = _lora_trainer(precision="mixed", remat=remat)
    (batch,) = _batches(t, 1)
    step = t.train_step.steps["all"] if remat else t.train_step
    copy = lambda tree: jax.tree.map(lambda x: x.copy(), tree)  # noqa: E731
    masters = _masters_in_f32(t)
    assert {x.dtype for x in jax.tree.leaves(masters)} == {np.dtype(np.float32)}
    cast_in_step = copy(t.state).replace(params=masters)
    now = copy(t.state)
    cast_in_step, m_cast = step(cast_in_step, batch)
    now, m_now = step(now, batch)
    for key in ("loss", "grad_norm"):
        np.testing.assert_array_equal(np.asarray(m_now[key]), np.asarray(m_cast[key]))
    for tree in ("params", "opt_state"):
        want = _leaves_by_path(getattr(cast_in_step, tree))
        got = _leaves_by_path(getattr(now, tree))
        assert list(got) == list(want)
        for path in want:
            if tree == "opt_state" or _is_adapter(path):
                np.testing.assert_array_equal(got[path], want[path], err_msg=path)


_LORA_RUN = {
    "version": 1.1,
    "kind": "operation",
    "name": "lora-mixed",
    "component": {
        "kind": "component",
        "name": "lora-mixed",
        "run": {
            "kind": "jaxjob",
            "program": {
                "model": {
                    "name": "transformer_lm",
                    "config": {
                        "dim": 64, "n_layers": 2, "n_heads": 4, "n_kv_heads": 2,
                        "hidden_dim": 96, "vocab_size": 160, "seq_len": 24,
                        "lora": {"rank": 4, "alpha": 8,
                                 "targets": ["q_proj", "k_proj", "v_proj", "o_proj"]},
                    },
                },
                "data": {
                    "name": "synthetic_text", "batchSize": 2,
                    "config": {"seq_len": 24, "vocab_size": 160},
                },
                "optimizer": {"name": "adamw", "learningRate": 0.01},
                "train": {"steps": 2, "logEvery": 1, "precision": "mixed",
                          "checkpointEvery": 2, "seed": 0},
            },
        },
    },
}


def _checkpoint_resumes_and_serves(tmp_path, saved):
    """A mixed LoRA run's checkpoint, as written before its frozen half was
    stored in bf16 (f32 masters) or as written now, resumes into the bf16
    frozen half, and is served in the masters' type, as before: serving
    reads the checkpoint into f32 params and computes in f32."""
    import yaml

    from polyaxon_tpu.models.generate import make_paged_cache
    from polyaxon_tpu.models.kv_pages import PagedKVLayout
    from polyaxon_tpu.parallel.sharding import _path_str
    from polyaxon_tpu.runtime.checkpoint import close_all, save_checkpoint
    from polyaxon_tpu.serving import ModelServer

    p = tmp_path / "lora.yaml"
    p.write_text(yaml.safe_dump(_LORA_RUN))
    store = RunStore()
    compiled = compile_operation(read_polyaxonfile(str(p)))
    assert Executor(store, devices=jax.devices()[:1]).execute(compiled) == "succeeded"
    close_all()
    ckdir = str((store.outputs_dir(compiled.run_uuid) / "checkpoints").resolve())
    program = V1Program.model_validate(_LORA_RUN["component"]["run"]["program"])
    resumed = Trainer(
        program.model_copy(update={"train": program.train.model_copy(
            update={"steps": 4, "resume": True})}),
        devices=jax.devices()[:1], checkpoint_dir=ckdir,
    )
    assert resumed.restore() == 2
    step = 2
    if saved == "f32-frozen":
        # the same run's state with the f32 masters the init made before
        masters = _leaves_by_path(_masters_in_f32(resumed))
        flat, treedef = jax.tree_util.tree_flatten_with_path(resumed.state.params)
        old = jax.tree_util.tree_unflatten(treedef, [
            x if _is_adapter(_path_str(path)) else jnp.asarray(masters[_path_str(path)])
            for path, x in flat
        ])
        step = 3
        save_checkpoint(ckdir, step, resumed.state.replace(params=old), wait=True)
        close_all()
        assert {x.dtype for x in jax.tree.leaves(old)} == {np.dtype(np.float32)}
    written = _leaves_by_path(
        old if saved == "f32-frozen" else resumed.state.params
    )

    def holds(params, frozen_type):
        """`params` hold what was written, each frozen leaf in `frozen_type`
        (a cast that loses nothing: the values are bf16's or f32's own)."""
        got = _leaves_by_path(params)
        assert list(got) == list(written)
        for path, x in written.items():
            want = x if _is_adapter(path) else x.astype(frozen_type)
            assert got[path].dtype == want.dtype, path
            np.testing.assert_array_equal(got[path], want, err_msg=path)

    again = Trainer(resumed.program, devices=jax.devices()[:1], checkpoint_dir=ckdir)
    assert again.restore() == step
    holds(again.state.params, jnp.bfloat16)
    (metrics,) = _run_steps(again, _batches(again, 1))
    assert np.isfinite(metrics["loss"])
    close_all()
    server = ModelServer.from_run(compiled.run_uuid, store=store)
    assert server.step == step
    # the trainer's paths and shapes; every float leaf in the masters' type
    served = _leaves_by_path(server.params)
    assert {p: x.shape for p, x in served.items()} == {
        p: x.shape for p, x in _leaves_by_path(again.state.params).items()
    }
    holds(server.params, jnp.float32)
    pool = make_paged_cache(
        server.module, server.params, PagedKVLayout(page_tokens=8, pool_pages=4)
    )
    assert {x.dtype for x in jax.tree.leaves(pool)} == {np.dtype(np.float32)}
    out = server.generate({"tokens": [[1, 2, 3]], "maxNewTokens": 2})
    assert len(out["tokens"][0]) == 5


@pytest.mark.parametrize(
    "check, kw",
    [
        pytest.param(_stores_its_frozen_half_in_the_type_it_reads,
                     dict(lora=True, precision="mixed", frozen_type="bfloat16"),
                     id="store-lora-mixed"),
        pytest.param(_stores_its_frozen_half_in_the_type_it_reads,
                     dict(lora=False, precision="mixed", frozen_type=None),
                     id="store-full-mixed"),
        pytest.param(_stores_its_frozen_half_in_the_type_it_reads,
                     dict(lora=True, precision="float32", frozen_type="float32"),
                     id="store-lora-float32"),
        pytest.param(_stores_its_frozen_half_in_the_type_it_reads,
                     dict(lora=True, precision="bfloat16", frozen_type="bfloat16"),
                     id="store-lora-bfloat16"),
        pytest.param(_reads_the_operands_f32_masters_gave, dict(remat=False),
                     id="step-plain"),
        pytest.param(_reads_the_operands_f32_masters_gave, dict(remat=True),
                     id="step-remat"),
        pytest.param(_checkpoint_resumes_and_serves, dict(saved="f32-frozen"),
                     id="checkpoint-f32-frozen"),
        pytest.param(_checkpoint_resumes_and_serves, dict(saved="compute-type"),
                     id="checkpoint-compute-type"),
    ],
)
def test_a_lora_steps_frozen_half_in_the_type_it_reads(check, kw, tmp_home, tmp_path):
    check(tmp_path, **kw)


# --------------------------------------------------------------------------
# `remat: true` keeps what the device can hold (PR 32): the step of every
# rung of a short ladder, most kept first, and the first whose compile the
# device's compiler accepts. On the CPU every rung fits, so a rung is run
# through its own `jax.jit` (`train_step.steps[rung]`) and a refusal is a
# stand-in's.


class _StandInStep:
    """What the ladder asks of a rung's `jax.jit`: `.lower(...).compile()`."""

    def __init__(self, rung, error=None):
        self.rung, self.error, self.lowerings = rung, error, 0

    def lower(self, state, batch):
        self.lowerings += 1
        return self

    def compile(self):
        if self.error is not None:
            raise self.error
        return self

    def memory_analysis(self):
        return None

    def __call__(self, state, batch):
        return self.rung, state, batch


def _refusal(rung):
    return jax.errors.JaxRuntimeError(
        f"RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        f"memory in memory space hbm ({rung}).\nUsed 19.13G of 15.75G hbm."
    )


@pytest.mark.parametrize("refused", [0, 1, 2, 3])
def test_remat_ladder_lands_on_the_first_rung_that_compiles(refused):
    from polyaxon_tpu.runtime.trainer import _RematLadder

    rungs = ("all", "block", "apply")
    steps = {
        r: _StandInStep(r, _refusal(r) if i < refused else None)
        for i, r in enumerate(rungs)
    }
    reports = []
    ladder = _RematLadder(steps, reports.append)
    if refused == len(rungs):
        with pytest.raises(jax.errors.JaxRuntimeError, match=r"hbm \(apply\)"):
            ladder("state", "batch")
        assert ladder.rung is None
    else:
        assert ladder("state", "batch") == (rungs[refused], "state", "batch")
        assert ladder.rung == rungs[refused]
        ladder("state", "batch")  # chosen once: no rung is lowered again
        assert ladder.lower("state", "batch") is steps[rungs[refused]]
    (report,) = reports
    assert report["rung"] == ladder.rung and report["ladder"] == list(rungs)
    tried = report["tried"]
    assert [t["rung"] for t in tried] == list(rungs[: refused + 1])
    assert [t["result"] for t in tried] == (["refused"] * refused + ["fits"])[: len(rungs)]
    for t in tried[:refused]:
        assert t["seconds"] >= 0 and t["compiler"].startswith("RESOURCE_EXHAUSTED")
        assert "\n" not in t["compiler"]
    # each rung tried was lowered once (the one that runs once more, for
    # `lower`), a rung below it never
    want = [1] * len(tried) + [0] * (len(rungs) - len(tried))
    if ladder.rung is not None:
        want[refused] += 1
    assert [s.lowerings for s in steps.values()] == want


@pytest.mark.parametrize(
    "error",
    [
        jax.errors.JaxRuntimeError("INTERNAL: Mosaic failed to compile TPU kernel"),
        ValueError("RESOURCE_EXHAUSTED is the compiler's word, not a ValueError's"),
    ],
    ids=["another-status", "another-type"],
)
def test_remat_ladder_lets_any_other_error_through(error):
    from polyaxon_tpu.runtime.trainer import _RematLadder

    steps = {"all": _StandInStep("all", error), "block": _StandInStep("block")}
    reports = []
    with pytest.raises(type(error)) as raised:
        _RematLadder(steps, reports.append)("state", "batch")
    assert raised.value is error
    assert steps["block"].lowerings == 0 and reports == []


# --------------------------------------------------------------------------
# Time to the first step, told by the program (PR 38): reads of the clock on
# calls set-up makes anyway, and when the step's executable exists one
# record of the finished set-up: five `train.startup.*` gauges of the
# process-global registry, a `startup` event, and the spans `build` > `init`
# and `first_step` > `rung` > `lower`, `compile`, written after the fact.


@pytest.mark.parametrize("refused", [0, 1, 2, 3])
def test_every_answer_of_a_ladder_splits_its_seconds(refused):
    """`seconds` stays what it was, a rung's lowering and compile together,
    on every answer now; `lower_seconds` and `compile_seconds` split it,
    and the instants they come from are kept for the spans."""
    from polyaxon_tpu.runtime.trainer import _RematLadder

    rungs = ("all", "block", "apply")
    steps = {
        r: _StandInStep(r, _refusal(r) if i < refused else None)
        for i, r in enumerate(rungs)
    }
    ladder = _RematLadder(steps, lambda choice: None)
    if refused == len(rungs):
        with pytest.raises(jax.errors.JaxRuntimeError):
            ladder("state", "batch")
    else:
        ladder("state", "batch")
    assert len(ladder.tried) == min(refused + 1, len(rungs))
    assert list(ladder.clock) == [t["rung"] for t in ladder.tried]
    at = ladder.began
    for t in ladder.tried:
        assert t["seconds"] == pytest.approx(t["lower_seconds"] + t["compile_seconds"], abs=1e-9)
        assert t["lower_seconds"] >= 0 and t["compile_seconds"] >= 0
        assert t["cache"] == "off"  # a stand-in asks no cache for anything
        t0, t1, t2 = ladder.clock[t["rung"]]
        assert at <= t0 <= t1 <= t2  # one rung after another
        assert t["lower_seconds"] == round(t1 - t0, 3)
        assert t["compile_seconds"] == round(t2 - t1, 3)
        at = t2


def test_the_ladders_live_frames_keep_their_size():
    """The frames that are live while the step is traced are as large as at
    1cb1803 (locals + stack, in words). CPython keeps frames on a data stack
    of 16 KiB chunks: a live frame that grows moves every frame under it,
    and on the chip's host five words more in `attempt` read 0.8 s of 10.4
    in InternLM2's lowering (PERF.md section 6, PR 38). A PR that changes a
    number here pairs `.lower()` on the chip before it hands in."""
    import sys

    from polyaxon_tpu.runtime.trainer import _RematLadder

    if sys.version_info[:2] != (3, 12):
        pytest.skip("the compiler's stack depths are CPython 3.12's")
    sizes = {
        name: (code.co_nlocals + len(code.co_cellvars) + len(code.co_freevars)
               + code.co_stacksize)
        for name in ("attempt", "_choose", "__call__")
        for code in [getattr(_RematLadder, name).__code__]
    }
    assert sizes == {"attempt": 15, "_choose": 12, "__call__": 7}


class _RefusingStep:
    """A rung's own `jax.jit`, really traced, lowered and compiled, and then
    refused as the device's compiler refuses (the CPU's never does)."""

    def __init__(self, step, rung):
        self.step, self.rung, self.lowered = step, rung, None

    def lower(self, state, batch):
        self.lowered = self.step.lower(state, batch)
        return self

    def compile(self):
        self.lowered.compile()
        raise _refusal(self.rung)


def _startup_trainer(kind, events):
    """A program without a ladder; a ladder whose first rung fits; one whose
    first rung is refused by the stand-in, or after a real compile."""
    if kind == "no-ladder":
        return Trainer(
            make_program(steps=2, logEvery=1), mesh_axes={"data": 1},
            devices=jax.devices()[:1], event_fn=lambda k, b: events.append((k, b)),
        )
    t = _lora_trainer(remat=True, events=events)
    if kind == "first-refused":
        t.train_step.steps["all"] = _StandInStep("all", _refusal("all"))
    elif kind == "first-compiled-and-refused":
        t.train_step.steps["all"] = _RefusingStep(t.train_step.steps["all"], "all")
    return t


def _startup_gauges():
    from polyaxon_tpu.telemetry import get_registry

    return {
        k.removeprefix("train.startup.").removesuffix("_seconds"): v
        for k, v in get_registry().snapshot().items() if k.startswith("train.startup.")
    }


def _spans(trainer):
    return [r for r in trainer.tracer.recent(512) if r["kind"] == "span"]


def _holds(parent, child):
    return (
        child["parent_id"] == parent["span_id"]
        and parent["ts"] <= child["ts"] + 1e-6
        and child["ts"] + child["dur_s"] <= parent["ts"] + parent["dur_s"] + 1e-6
    )


@pytest.mark.parametrize("kind", ["first-fits", "first-refused"])
def test_startup_record_of_a_ladder(kind):
    """Nothing is reported before the step's executable exists; then the
    five gauges, one `startup` event and the spans, all from the same reads
    of the clock; a later step or `lower()` leaves them alone."""
    from polyaxon_tpu.telemetry import process_age

    events = []
    t = _startup_trainer(kind, events)
    assert not [e for e in events if e[0] == "startup"]
    assert not [r for r in _spans(t) if r["name"] in ("build", "first_step")]
    batches = _batches(t, 3)
    _run_steps(t, batches[:1])

    ((_, record),) = [e for e in events if e[0] == "startup"]
    remat = next(b for k, b in events if k == "remat")
    tried = remat["tried"]
    refused = [a for a in tried if a["result"] == "refused"]
    assert len(refused) == (kind == "first-refused")
    assert record["rung"] == t.train_step.rung == ("block" if refused else "all")
    assert record["cache"] == "off"  # the CPU backend keeps no persistent cache
    assert record["step_lower_seconds"] == pytest.approx(
        sum(a["lower_seconds"] for a in tried), abs=1e-9)
    assert record["step_compile_seconds"] == tried[-1]["compile_seconds"]
    # 0.0 where none was refused: the PR that stops paying it reads 43 -> 0
    assert record["refused_compile_seconds"] == (
        refused[0]["compile_seconds"] if refused else 0.0)
    assert isinstance(record["refused_compile_seconds"], float)
    assert 0 < record["init_seconds"] <= record["build_seconds"]
    gauges = _startup_gauges()
    for name in ("build", "step_lower", "step_compile", "refused_compile"):
        assert gauges[name] == record[f"{name}_seconds"], name
    assert "init" not in gauges  # a builder's reading: no gauge, no reader
    if process_age() is not None:  # Linux
        assert 0 < record["before_trainer_seconds"] == gauges["before_trainer"]
        assert record["total_s"] >= record["before_trainer_seconds"]  # the age now
    (mark,) = [r for r in t.tracer.recent(512) if r["name"] == "startup"]
    assert mark["kind"] == "event" and mark["attrs"] == record

    spans = _spans(t)
    (build,), (init,), (first,) = (
        [r for r in spans if r["name"] == n] for n in ("build", "init", "first_step"))
    assert build["parent_id"] is None and first["parent_id"] is None
    assert _holds(build, init)
    assert round(build["dur_s"], 3) == record["build_seconds"]
    assert round(init["dur_s"], 3) == record["init_seconds"]
    assert build["ts"] + build["dur_s"] <= first["ts"] + 1e-6
    assert first["attrs"] == {"rung": record["rung"], "rungs_tried": len(tried)}
    rungs = [r for r in spans if r["name"] == "rung"]
    assert [r["attrs"]["rung"] for r in rungs] == [a["rung"] for a in tried]
    for rung, answer in zip(rungs, tried):
        assert _holds(first, rung)
        assert rung["attrs"]["result"] == answer["result"]
        assert ("compiler" in rung["attrs"]) == (answer["result"] == "refused")
        lower, compile_ = (
            next(r for r in spans if r["parent_id"] == rung["span_id"] and r["name"] == n)
            for n in ("lower", "compile")
        )
        assert _holds(rung, lower) and _holds(rung, compile_)
        assert lower["ts"] + lower["dur_s"] <= compile_["ts"] + 1e-6
        assert round(lower["dur_s"], 3) == answer["lower_seconds"]
        assert round(compile_["dur_s"], 3) == answer["compile_seconds"]
        assert compile_["attrs"] == {"cache": answer["cache"]}

    _run_steps(t, batches[1:])
    t.train_step.lower(t.state, batches[0])
    assert len([e for e in events if e[0] == "startup"]) == 1
    assert _spans(t) == spans
    assert _startup_gauges() == gauges
    t.close()


def test_startup_record_without_a_ladder():
    """`remat: false` keeps its plain `jax.jit`: `before_trainer` and `build`
    when `__init__` returns, lowering and compile left to `xla.*_seconds`."""
    from polyaxon_tpu.runtime.trainer import _RematLadder
    from polyaxon_tpu.telemetry import process_age

    before = _startup_gauges()
    events = []
    t = _startup_trainer("no-ladder", events)
    assert not isinstance(t.train_step, _RematLadder)
    assert type(t.train_step).__module__.startswith("jax")  # no wrapper before the jit
    ((_, record),) = [e for e in events if e[0] == "startup"]
    want = {"build_seconds", "init_seconds"}
    if process_age() is not None:
        want |= {"before_trainer_seconds", "total_s"}
    assert set(record) == want
    gauges = _startup_gauges()
    assert gauges["build"] == record["build_seconds"]
    assert gauges.get("before_trainer") == record.get("before_trainer_seconds")
    for name in ("step_lower", "step_compile", "refused_compile"):
        assert gauges.get(name) == before.get(name), name  # not this Trainer's to set
    spans = _spans(t)
    assert [r["name"] for r in spans] == ["build", "init"] and _holds(*spans)
    _run_steps(t, _batches(t, 2))
    assert len([e for e in events if e[0] == "startup"]) == 1
    assert [r["name"] for r in _spans(t) if r["name"] in ("build", "init", "first_step")] == [
        "build", "init"]
    assert _startup_gauges() == gauges
    t.close()


@pytest.mark.parametrize(
    "kind,built", [("first-fits", 1), ("first-compiled-and-refused", 2)]
)
def test_first_step_builds_the_step_once_a_rung(kind, built):
    """A guard on counts: with a listener of the test's own on JAX's three
    duration events, a Trainer's first step traces, lowers and compiles
    `step_fn` once where rung `all` fits and twice where it is refused (no
    second `.trace()`, `.lower()` or `eval_shape` of the step for a timing's
    sake), and later steps build nothing."""
    import jax.monitoring

    seen, listening = [], [True]

    def listener(event, duration, **kw):
        if listening[0] and "step_fn" in str(kw.get("fun_name")):
            seen.append(event.rsplit("/", 1)[1])

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        t = _startup_trainer(kind, [])
        batches = _batches(t, 3)
        assert seen == []  # `__init__` builds the wrappers only
        _run_steps(t, batches[:1])
        assert sorted(seen) == sorted(
            ["jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
             "backend_compile_duration"] * built
        )
        _run_steps(t, batches[1:])
        assert len(seen) == 3 * built
        t.close()
    finally:
        listening[0] = False  # a listener cannot be taken off again


def test_trainer_reports_the_rung_that_runs():
    import json

    events = []
    t = _lora_trainer(remat=True, events=events)
    assert list(t.train_step.steps) == ["all", "block"]
    assert not [e for e in events if e[0] == "remat"]  # chosen at the first step
    _run_steps(t, _batches(t, 1))
    ((_, body),) = [e for e in events if e[0] == "remat"]
    assert body["rung"] == "all" and body["ladder"] == ["all", "block"]
    assert [(a["rung"], a["result"]) for a in body["tried"]] == [("all", "fits")]
    snap = t.telemetry.snapshot()
    assert snap["train.remat.rung"] == 0
    assert snap["train.remat.refused_compiles"] == 0
    (mark,) = [r for r in t.tracer.recent(64) if r["name"] == "remat"]
    assert mark["attrs"]["rung"] == "all"
    assert json.loads(mark["attrs"]["tried"]) == body["tried"]


@pytest.mark.parametrize(
    "model,train,rungs",
    [
        ({}, {}, None),
        ({}, {"remat": True}, ["all", "block"]),
        ({"scan_layers": True}, {"remat": True}, ["all", "block"]),
        ({"pipeline_stages": 2}, {"remat": True}, ["all", "apply"]),
        ({}, {"remat": True, "rematPolicy": "dots"}, None),
        ({}, {"rematPolicy": "nothing"}, None),
    ],
    ids=["off", "blocks", "scan", "pipelined", "policy-and-remat", "policy"],
)
def test_which_ladder_a_program_gets(model, train, rungs):
    """A ladder only under `remat: true` with no explicit policy; its second
    rung is the module's own where the bundle offers one."""
    from polyaxon_tpu.runtime.trainer import _RematLadder

    t = _lora_trainer(model=model, **train)
    if rungs is None:
        assert not isinstance(t.train_step, _RematLadder)
    else:
        assert list(t.train_step.steps) == rungs


def test_mlp_has_no_block_rung():
    """A module that names no block boundary: `all`, then the whole apply."""
    from polyaxon_tpu.runtime.trainer import Trainer

    p = make_program(steps=2, logEvery=1)
    p.train.remat = True
    t = Trainer(p, mesh_axes={"data": 8})
    assert list(t.train_step.steps) == ["all", "apply"]
    result = t.run()
    assert t.train_step.rung == "all"
    assert np.isfinite(result.history[-1]["loss"])


def _dot_generals(step, trainer, batch) -> int:
    return step.lower(trainer.state, batch).as_text().count("stablehlo.dot_general")


@pytest.mark.parametrize("scan", [False, True], ids=["loop", "scan"])
def test_products_of_each_rung(scan):
    """Rung `all` is the step of `remat: false`; `block` computes more
    products than it and no more than a checkpoint of the whole apply; no
    rung computes the weight gradient of a frozen kernel (PR 28)."""
    model = {"scan_layers": scan}
    t = _lora_trainer(model=model, remat=True)
    plain = _lora_trainer(model=model)
    whole = _lora_trainer(model=model, rematPolicy="nothing")
    (batch,) = _batches(t, 1)
    n = {rung: _dot_generals(step, t, batch) for rung, step in t.train_step.steps.items()}
    assert n["all"] == _dot_generals(plain.train_step, plain, batch)
    assert n["all"] < n["block"] <= _dot_generals(whole.train_step, whole, batch)
    frozen = {
        shape
        for path, x in _leaves_by_path(t.state.params).items()
        if not _is_adapter(path) and x.ndim >= 2
        for shape in (x.shape[-2:], x.shape[-2:][::-1])
    }
    assert {(64, 64), (64, 32), (64, 96), (96, 64), (64, 160)} <= frozen
    for rung, step in t.train_step.steps.items():
        products = {s[-2:] for s in _product_shapes(t, batch, step)}
        assert not products & frozen, (rung, sorted(products & frozen))
        assert {(64, 4), (4, 64), (4, 32)} <= products, rung


def _three_steps_on(rung, **kw):
    """(losses, adapter leaves) after three steps of one rung's own step."""
    t = _lora_trainer(remat=True, **kw)
    step, losses = t.train_step.steps[rung], []
    for b in _batches(t, 3):
        t.state, m = step(t.state, b)
        losses.append(float(m["loss"]))
    return losses, {
        p: x for p, x in _leaves_by_path(t.state.params).items() if _is_adapter(p)
    }


@pytest.mark.parametrize(
    "model",
    [{}, {"scan_layers": True}, {"dropout_rate": 0.1}],
    ids=["loop", "scan", "dropout"],
)
def test_every_rung_takes_the_same_three_steps(model):
    want_losses, want = _three_steps_on("all", model=model)
    got_losses, got = _three_steps_on("block", model=model)
    np.testing.assert_allclose(got_losses, want_losses, rtol=1e-6)
    assert want and list(got) == list(want)
    for path in want:
        np.testing.assert_allclose(
            got[path], want[path], rtol=1e-4, atol=1e-6, err_msg=path
        )


def test_a_fitting_first_rung_is_traced_lowered_and_compiled_once():
    import jax.monitoring

    seen, listening = [], [True]

    def on(event, duration, fun_name=None, **_):  # a listener cannot be taken off
        if listening and fun_name in ("step_fn", "jit(step_fn)"):
            seen.append(event.rsplit("/", 1)[-1])

    t = _lora_trainer(remat=True)
    batches = _batches(t, 3)
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        _run_steps(t, batches)
    finally:
        listening.clear()
    assert sorted(seen) == [
        "backend_compile_duration", "jaxpr_to_mlir_module_duration",
        "jaxpr_trace_duration",
    ]
    assert t.train_step.rung == "all"
    # and the benchmark's question of the step that runs is still answered
    lowered = t.train_step.lower(t.state, batches[0])
    assert lowered.as_text().count("stablehlo.dot_general") == _dot_generals(
        t.train_step.steps["all"], t, batches[0]
    )
    assert lowered.compile().memory_analysis() is not None
