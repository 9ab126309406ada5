"""Distributed-execution integration tests.

These run REAL sharded computation on 8 virtual CPU devices in a
subprocess (the device count is pinned at jax init, so the main test
process stays single-device).  They verify semantics the dry-run can't:
DP gradient agreement, TP logit equivalence, elastic remesh restore,
and hierarchical/compressed reduction numerics under shard_map.
"""

import os
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(body: str, devices: int = 8, timeout: int = 600):
    prog = textwrap.dedent(
        f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        """
    ) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=REPO,
    )
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    return res.stdout


class TestShardedTraining:
    def test_dp_tp_train_step_matches_single_device(self):
        run_in_subprocess(
            """
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import get_arch
            from repro.sharding.partition import batch_shardings, state_shardings
            from repro.train.optimizer import adamw
            from repro.train.train_step import init_state, make_train_step

            cfg = get_arch("tinyllama-1.1b").smoke
            opt = adamw(lr=1e-3)
            state = init_state(cfg, opt, seed=0)
            step = make_train_step(cfg, opt)
            batch = {"tokens": jnp.asarray(
                np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 16)))}

            # single-device reference
            ref_state, ref_metrics = jax.jit(step)(state, batch)

            # 4x2 (data, model) mesh
            mesh = jax.make_mesh((4, 2), ("data", "model"),
                                 axis_types=(jax.sharding.AxisType.Auto,)*2)
            st_sh = state_shardings(cfg, mesh, jax.eval_shape(lambda: init_state(cfg, opt, seed=0)))
            b_sh = batch_shardings(cfg, mesh, batch)
            with mesh:
                sharded = jax.jit(step, in_shardings=(st_sh, b_sh),
                                  out_shardings=(st_sh, None))(state, batch)
            sh_state, sh_metrics = sharded
            np.testing.assert_allclose(float(sh_metrics["loss"]),
                                       float(ref_metrics["loss"]), rtol=1e-4)
            a = np.asarray(jax.tree.leaves(ref_state.params)[0])
            b = np.asarray(jax.tree.leaves(sh_state.params)[0])
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)
            print("DP/TP == single-device OK")
            """
        )

    def test_moe_expert_parallel_runs(self):
        run_in_subprocess(
            """
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import get_arch
            from repro.sharding.partition import batch_shardings, state_shardings
            from repro.train.optimizer import adamw
            from repro.train.train_step import init_state, make_train_step

            cfg = get_arch("deepseek-v3-671b").smoke  # MLA + MoE family
            opt = adamw(lr=1e-3)
            state = init_state(cfg, opt, seed=0)
            step = make_train_step(cfg, opt)
            batch = {"tokens": jnp.asarray(
                np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 16)))}
            mesh = jax.make_mesh((2, 4), ("data", "model"),
                                 axis_types=(jax.sharding.AxisType.Auto,)*2)
            st_sh = state_shardings(cfg, mesh, jax.eval_shape(lambda: init_state(cfg, opt, seed=0)))
            b_sh = batch_shardings(cfg, mesh, batch)
            with mesh:
                (new_state, metrics) = jax.jit(
                    step, in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None)
                )(state, batch)
            assert np.isfinite(float(metrics["loss"]))
            print("EP MoE sharded step OK", float(metrics["loss"]))
            """
        )

    def test_sharded_decode_sequence_cache(self):
        run_in_subprocess(
            """
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import get_arch
            from repro.models import DecoderLM
            from repro.serve.serve_step import make_cache_factory, make_decode_step
            from repro.sharding.partition import cache_shardings, param_shardings

            cfg = get_arch("tinyllama-1.1b").smoke
            m = DecoderLM(cfg)
            params = m.init(0)
            decode = make_decode_step(cfg)
            # single-device reference
            cache0 = make_cache_factory(cfg)(batch=1, max_len=64)
            ref, _ = jax.jit(decode)(params, cache0, jnp.zeros((1,1), jnp.int32))

            mesh = jax.make_mesh((8, 1), ("data", "model"),
                                 axis_types=(jax.sharding.AxisType.Auto,)*2)
            p_sh = param_shardings(cfg, mesh, jax.eval_shape(lambda: m.init(0)))
            c_sh = cache_shardings(cfg, mesh, jax.eval_shape(
                lambda: make_cache_factory(cfg)(batch=1, max_len=64)))
            with mesh:
                out, _ = jax.jit(decode, in_shardings=(p_sh, c_sh, None),
                                 out_shardings=None)(params, cache0,
                                                     jnp.zeros((1,1), jnp.int32))
            np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                       rtol=2e-3, atol=2e-3)
            print("sequence-sharded decode == single-device OK")
            """
        )


class TestHierarchicalCollectives:
    def test_hierarchical_psum_equals_flat(self):
        run_in_subprocess(
            """
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P
            from jax import shard_map
            from repro.train.compression import hierarchical_psum

            mesh = jax.make_mesh((2, 4), ("pod", "data"),
                                 axis_types=(jax.sharding.AxisType.Auto,)*2)
            x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)

            flat = shard_map(lambda v: jax.lax.psum(v, ("data", "pod")),
                             mesh=mesh, in_specs=P(("pod","data")),
                             out_specs=P(("pod","data")))(x)
            hier = shard_map(lambda v: hierarchical_psum(v, "data", "pod"),
                             mesh=mesh, in_specs=P(("pod","data")),
                             out_specs=P(("pod","data")))(x)
            np.testing.assert_allclose(np.asarray(flat), np.asarray(hier), rtol=1e-6)
            print("hierarchical psum OK")
            """
        )

    def test_compressed_cross_pod_mean(self):
        run_in_subprocess(
            """
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P
            from jax import shard_map
            from repro.train.compression import compressed_cross_pod_mean, ef_init

            mesh = jax.make_mesh((4, 2), ("pod", "data"),
                                 axis_types=(jax.sharding.AxisType.Auto,)*2)
            rng = np.random.default_rng(0)
            g = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))

            def body(gv):
                grads = {"w": gv}
                ef = ef_init(grads)
                reduced, ef = compressed_cross_pod_mean(grads, ef, "pod")
                return reduced["w"]

            out = shard_map(body, mesh=mesh, in_specs=P("pod", None),
                            out_specs=P("pod", None))(g)
            want = np.broadcast_to(np.asarray(g).reshape(4, 1, 32).mean(axis=0),
                                   (4, 1, 32)).reshape(4, 32)
            # int8 quantization: loose tolerance, but structure preserved
            np.testing.assert_allclose(np.asarray(out), want, atol=0.05)
            print("compressed cross-pod mean OK")
            """
        )


class TestElasticRemesh:
    def test_checkpoint_restores_onto_different_mesh(self):
        run_in_subprocess(
            """
            import os, tempfile
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import get_arch
            from repro.sharding.partition import state_shardings
            from repro.train.checkpoint import save_checkpoint
            from repro.train.fault_tolerance import elastic_restore
            from repro.train.optimizer import adamw
            from repro.train.train_step import init_state

            cfg = get_arch("granite-3-2b").smoke
            opt = adamw(lr=1e-3)
            state = init_state(cfg, opt, seed=0)
            with tempfile.TemporaryDirectory() as d:
                save_checkpoint(d, 42, state)
                # restore onto a 2x4 mesh (as if scaled from 1 device to 8)
                mesh = jax.make_mesh((2, 4), ("data", "model"),
                                     axis_types=(jax.sharding.AxisType.Auto,)*2)
                like = jax.eval_shape(lambda: init_state(cfg, opt, seed=0))
                sh = state_shardings(cfg, mesh, like)
                step, restored = elastic_restore(d, like, sh)
                assert step == 42
                leaf = jax.tree.leaves(restored.params)[0]
                assert len(leaf.sharding.device_set) > 1
                orig = jax.tree.leaves(state.params)[0]
                np.testing.assert_allclose(np.asarray(leaf), np.asarray(orig))
            print("elastic remesh restore OK")
            """
        )
