"""Distributed semantics on fake CPU devices (subprocess so the device
count is set before jax initializes): sharded train step, elastic
restore across mesh shapes, compressed psum in shard_map."""
import os
import subprocess
import sys
import textwrap


_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sharded_train_step_runs_and_matches_single_device():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.launch.train import TrainConfig, Trainer
        from repro.data.pipeline import DataConfig, SyntheticPipeline
        from repro.optim.adamw import AdamWConfig

        cfg = get_config("quickstart", smoke=True)
        tcfg = TrainConfig(steps=3, log_every=100,
                           ckpt_dir="/tmp/rt_mesh_ckpt",
                           optimizer=AdamWConfig(lr=1e-3, total_steps=3))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        pipe = SyntheticPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                            seq_len=32, global_batch=8))
        tr = Trainer(cfg, tcfg, mesh=mesh)
        params, opt, hist = tr.run(pipe)
        l_mesh = hist[0]["loss"]

        import shutil; shutil.rmtree("/tmp/rt_mesh_ckpt")
        pipe2 = SyntheticPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                             seq_len=32, global_batch=8))
        tr2 = Trainer(cfg, tcfg, mesh=None)
        _, _, hist2 = tr2.run(pipe2)
        np.testing.assert_allclose(l_mesh, hist2[0]["loss"], rtol=1e-4)
        import shutil; shutil.rmtree("/tmp/rt_mesh_ckpt")
        print("OK", l_mesh)
    """)
    assert "OK" in out


def test_elastic_restore_across_mesh_shapes():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.checkpoint.manager import CheckpointManager
        from repro.configs import get_config
        from repro.distributed import sharding as S
        from repro.distributed.elastic import elastic_restore, candidate_meshes
        from repro.models import abstract_init, init, loss_fn

        cfg = get_config("quickstart", smoke=True)
        params = init(jax.random.PRNGKey(0), cfg)
        mgr = CheckpointManager("/tmp/rt_elastic", keep=1)
        mgr.save(7, params)

        # restore onto an 8-device mesh, then onto a degraded 6-device mesh
        for ndev in (8, 6):
            devs = jax.devices()[:ndev]
            mesh, step, restored, meta = elastic_restore(
                mgr, abstract_init(cfg), cfg,
                mesh=None if ndev == 8 else
                jax.make_mesh((3, 2), ("data", "model"), devices=devs[:6]))
            assert step == 7
            for a, b in zip(jax.tree.leaves(params),
                            jax.tree.leaves(restored)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert candidate_meshes(6)[0][0] * candidate_meshes(6)[0][1] == 6
        import shutil; shutil.rmtree("/tmp/rt_elastic")
        print("OK")
    """)
    assert "OK" in out


def test_compressed_psum_in_shard_map():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.optim.compression import compressed_psum_grads, init_residual

        mesh = make_mesh((8,), ("data",))
        grads = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        res = init_residual(grads)

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(P("data", None), P("data", None)),
                 out_specs=(P("data", None), P("data", None)))
        def sync(g, r):
            sg, nr = compressed_psum_grads({"w": g}, {"w": r}, ("data",))
            return sg["w"], nr["w"]

        sg, nr = sync(grads["w"], res["w"])
        # exact mean of the 8 per-device shards (each 1x8 row)
        want = jnp.mean(grads["w"], axis=0, keepdims=True)
        want = jnp.broadcast_to(want, (8, 8))
        np.testing.assert_allclose(np.asarray(sg), np.asarray(want),
                                   rtol=0.02, atol=0.05)
        print("OK")
    """)
    assert "OK" in out


def test_dryrun_single_cell_quickstart_scale():
    # an end-to-end mini dry-run on 8 fake devices: every piece of the
    # dryrun path (specs, shardings, walker) below production scale
    out = run_sub("""
        import jax, numpy as np
        from repro.launch.dryrun import input_specs, model_flops
        from repro.configs import get_config
        from repro.distributed import sharding as S
        from repro.models import model as model_lib
        from repro.launch import hlo_analysis
        from repro.launch.train import TrainConfig, make_train_step
        from repro.optim.adamw import AdamWConfig, init_state
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = get_config("quickstart", smoke=True)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        abs_params = model_lib.abstract_init(cfg)
        pshard = S.named_sharding_tree(
            S.param_spec_tree(abs_params, cfg), mesh)
        tcfg = TrainConfig(grad_accum=1, optimizer=AdamWConfig())
        step = make_train_step(cfg, tcfg)
        abs_opt = jax.eval_shape(
            lambda: init_state(abs_params, tcfg.optimizer))
        oshard = {"m": pshard, "v": pshard,
                  "count": NamedSharding(mesh, P())}
        batch = {"inputs": jax.ShapeDtypeStruct((8, 64), "int32"),
                 "labels": jax.ShapeDtypeStruct((8, 64), "int32")}
        bshard = {k: NamedSharding(mesh, P(("data",), None))
                  for k in batch}
        with mesh:
            c = jax.jit(step, in_shardings=(pshard, oshard, bshard)) \\
                .lower(abs_params, abs_opt, batch).compile()
        cost = hlo_analysis.analyze(c.as_text())
        useful = model_flops(cfg, "train_4k")  # not used, just call it
        assert cost.flops > 0 and cost.coll_wire_bytes > 0
        assert c.memory_analysis().temp_size_in_bytes > 0
        print("OK flops=%.2e" % cost.flops)
    """)
    assert "OK" in out


def test_dryrun_import_leaves_platform_and_flags_alone():
    # only the dry-run's own process (and the children it starts) is
    # pinned to the CPU: a process that imports its helpers keeps the
    # platform and XLA_FLAGS it was started with
    code = """
        import os
        import jax
        import repro.launch.dryrun as dryrun
        assert "JAX_PLATFORMS" not in os.environ, os.environ["JAX_PLATFORMS"]
        assert os.environ.get("XLA_FLAGS") == "--xla_dump_to=/dev/null"
        assert jax.config.jax_platforms is None
        child = dryrun._cpu_env(os.environ)
        assert child["JAX_PLATFORMS"] == "cpu"
        assert child["XLA_FLAGS"].split() == [
            "--xla_dump_to=/dev/null",
            "--xla_force_host_platform_device_count=512"]
        print("OK")
    """
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["XLA_FLAGS"] = "--xla_dump_to=/dev/null"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr


def test_dryrun_main_pins_the_cpu():
    # run as a module, the dry-run pins its own process to the CPU even
    # though the package import has already loaded jax
    code = """
        import runpy, sys
        sys.argv = ["dryrun", "--help"]
        try:
            runpy.run_module("repro.launch.dryrun", run_name="__main__")
        except SystemExit:
            pass
        import jax, os
        assert jax.config.jax_platforms == "cpu"
        assert os.environ["JAX_PLATFORMS"] == "cpu"
        assert "device_count=512" in os.environ["XLA_FLAGS"]
        print("OK")
    """
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr
