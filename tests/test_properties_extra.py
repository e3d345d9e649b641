"""Deeper property coverage: MoE dispatch invariants under hypothesis,
flash-attention equivalence sweep, elastic re-meshing."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.configs.registry import smoke_config
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib


@settings(max_examples=10, deadline=None)
@given(
    n_experts=st.sampled_from([4, 8, 16]),
    topk=st.integers(1, 3),
    seed=st.integers(0, 2**31 - 1),
)
def test_moe_dispatch_invariants(n_experts, topk, seed):
    """With generous capacity the sorted dispatch equals the dense oracle
    for ANY router outcome; with tight capacity outputs only ever shrink
    (drops), never grow or corrupt."""
    cfg = dataclasses.replace(
        smoke_config("deepseek-moe-16b"), n_experts=n_experts, moe_topk=topk,
        d_model=32, d_expert=16, moe_capacity_factor=8.0, dtype="float32",
    )
    key = jax.random.key(seed)
    p = moe_lib.init_moe(key, cfg, None)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, cfg.d_model))
    ref, _ = moe_lib.moe_ref(x, p, cfg)
    out, _ = moe_lib.moe_forward(x, p, cfg, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-5, atol=3e-5)

    tight = dataclasses.replace(cfg, moe_capacity_factor=0.4)
    out_t, _ = moe_lib.moe_forward(x, p, tight, None)
    assert np.isfinite(np.asarray(out_t)).all()


@settings(max_examples=8, deadline=None)
@given(
    s=st.sampled_from([1024, 2048]),
    h=st.sampled_from([2, 4]),
    kv=st.sampled_from([1, 2]),
    causal=st.booleans(),
    seed=st.integers(0, 2**31 - 1),
)
def test_flash_attention_equivalence_sweep(s, h, kv, causal, seed):
    if h % kv:
        h = kv
    rng = np.random.default_rng(seed)
    dh = 16
    q = jnp.asarray(rng.standard_normal((1, s, h, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, s, kv, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, s, kv, dh)), jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)

    class _C:
        pass

    ref = attn_lib._chunked_attn(q, k, v, _C(), causal=causal, window=0,
                                 q_positions=pos, k_positions=pos, scale=dh ** -0.5)
    for fn in (attn_lib._flash_attn_train, attn_lib._flash_attn_pairs):
        out = fn(q, k, v, causal=causal, scale=dh ** -0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_elastic_remesh_lowers_on_shrunk_device_set():
    """Elastic scaling: the same train step lowers on meshes built from
    different live-device counts (launch.mesh.make_mesh_for)."""
    import os
    import subprocess
    import sys
    import textwrap

    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    code = textwrap.dedent("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.configs.registry import smoke_config
        from repro.launch.mesh import make_mesh_for
        from repro.models.model import Model
        from repro.optim.adamw import OptConfig
        from repro.sharding.spec import from_mesh
        from repro.train.step import TrainConfig, make_train_step, init_train_state

        cfg = smoke_config("qwen3-4b")
        tcfg = TrainConfig(opt=OptConfig())
        for n in (8, 4):  # simulate losing half the fleet
            mesh = make_mesh_for(n)
            axes = from_mesh(mesh)
            m = Model(cfg, axes)
            params, opt = init_train_state(m, tcfg, jax.random.key(0))
            batch = {"tokens": jnp.zeros((1, 4, 32), jnp.int32),
                     "labels": jnp.zeros((1, 4, 32), jnp.int32)}
            with jax.set_mesh(mesh):
                c = jax.jit(make_train_step(m, tcfg)).lower(
                    params, opt, jnp.int32(0), batch).compile()
            print("lowered on", n, "devices:", mesh.devices.shape)
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    assert "OK" in r.stdout
