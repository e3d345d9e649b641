"""Multi-device integration: shard_map distributed sort / MoE / train step
on 8 virtual host devices. Each test runs in a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main pytest
process keeps seeing one device (per the dry-run isolation rule)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_distributed_sort_correct_and_balanced():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import SortConfig, distributed_sort
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        rng = np.random.default_rng(2)
        cfg = SortConfig(tile=256, capacity_factor=1.5)
        for name, x in [
            ("uniform", rng.uniform(0, 1, 8192).astype(np.float32)),
            ("dup3", rng.integers(0, 3, 8192).astype(np.int32)),
        ]:
            r = distributed_sort(jnp.asarray(x), mesh, "data", cfg)
            assert not np.asarray(r.overflowed).any()
            counts = np.asarray(r.count)
            values = np.asarray(r.values)
            got = np.concatenate([values[i, :counts[i]] for i in range(4)])
            np.testing.assert_array_equal(got, np.sort(x))
            assert counts.max() / counts.mean() < 1.05
        print("OK")
    """)
    assert "OK" in out


def test_distributed_sort_multi_axis_pod():
    """Sort over the ("data","model") axis tuple — the multi-pod pattern."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import SortConfig, distributed_sort_kv
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        rng = np.random.default_rng(0)
        keys = rng.integers(0, 10, 8192).astype(np.int32)
        vals = np.arange(8192, dtype=np.int32)
        r = distributed_sort_kv(jnp.asarray(keys), jnp.asarray(vals), mesh,
                                ("data", "model"), SortConfig(capacity_factor=1.5))
        assert not np.asarray(r.overflowed).any()
        counts = np.asarray(r.count)
        rk, rv = np.asarray(r.keys), np.asarray(r.values)
        k = np.concatenate([rk[i, :counts[i]] for i in range(8)])
        v = np.concatenate([rv[i, :counts[i]] for i in range(8)])
        np.testing.assert_array_equal(k, np.sort(keys))
        np.testing.assert_array_equal(keys[v], k)
        np.testing.assert_array_equal(np.sort(v), np.arange(8192))
        print("OK")
    """)
    assert "OK" in out


def test_distributed_moe_matches_oracle():
    out = _run("""
        import dataclasses, numpy as np, jax, jax.numpy as jnp
        from repro.configs.registry import smoke_config
        from repro.models import moe as moe_lib
        from repro.sharding.spec import from_mesh
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = dataclasses.replace(smoke_config("deepseek-moe-16b"),
                                  moe_capacity_factor=8.0, dtype="float32")
        p = moe_lib.init_moe(jax.random.key(1), cfg, None)
        x = jax.random.normal(jax.random.key(2), (4, 16, cfg.d_model), jnp.float32)
        out_ref, _ = moe_lib.moe_ref(x, p, cfg)
        for expert_2d in (False, True):
            axes = from_mesh(mesh, expert_2d=expert_2d)
            with jax.set_mesh(mesh):
                out, aux = jax.jit(lambda x, p: moe_lib.moe_forward(x, p, cfg, axes))(x, p)
            np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                                       rtol=2e-5, atol=2e-5)
        print("OK")
    """)
    assert "OK" in out


def test_distributed_train_step_runs_and_matches_single():
    """One sharded train step on a (pod,data,model) mesh: loss finite and
    equal (within bf16 tolerance) to the unsharded step."""
    out = _run("""
        import dataclasses, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding
        from repro.configs.registry import smoke_config
        from repro.models.model import Model
        from repro.optim.adamw import OptConfig
        from repro.sharding import rules
        from repro.sharding.spec import from_mesh
        from repro.train.step import TrainConfig, make_train_step

        cfg = dataclasses.replace(smoke_config("deepseek-moe-16b"), remat=True)
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 4, 32)), jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab, (2, 4, 32)), jnp.int32),
        }
        tcfg = TrainConfig(opt=OptConfig(peak_lr=1e-3, warmup_steps=1, total_steps=10))

        # single-device reference
        m0 = Model(cfg, None)
        from repro.train.step import init_train_state
        params, opt_state = init_train_state(m0, tcfg, jax.random.key(0))
        _, _, met0 = jax.jit(make_train_step(m0, tcfg))(params, opt_state, jnp.int32(0), batch)

        # sharded
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        axes = from_mesh(mesh)
        m1 = Model(cfg, axes)
        pspecs = rules.param_specs(jax.eval_shape(lambda: params), cfg, axes)
        shard = lambda t, s: jax.tree.map(
            lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)), t, s,
            is_leaf=lambda x: hasattr(x, "shape"))
        with jax.set_mesh(mesh):
            p1 = shard(params, pspecs)
            _, _, met1 = jax.jit(make_train_step(m1, tcfg))(p1, opt_state, jnp.int32(0), batch)
        l0, l1 = float(met0["loss"]), float(met1["loss"])
        assert np.isfinite(l1), l1
        assert abs(l0 - l1) < 0.05 * abs(l0), (l0, l1)
        print("OK", l0, l1)
    """)
    assert "OK" in out


def test_compressed_psum_close_to_exact():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.optim.compress import compressed_psum_mean, CHUNK
        mesh = jax.make_mesh((8,), ("data",))
        N = CHUNK * 8 * 4
        rng = np.random.default_rng(1)
        x = rng.standard_normal((8, N)).astype(np.float32)
        f = jax.shard_map(lambda v: compressed_psum_mean(v[0], "data")[None],
                          mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                          check_vma=False)
        got = np.asarray(f(jnp.asarray(x)))
        exact = x.mean(0)
        rel = np.abs(got - exact).max() / np.abs(exact).max()
        assert rel < 0.02, rel
        print("OK", rel)
    """)
    assert "OK" in out
