"""Distributed sort on a real device mesh through the unified front end
(`repro.sort(x, where=mesh)` -> shard_map + jax.lax collectives), over
every device JAX sees: the chips of a TPU host, or virtual CPU devices.

    python examples/sort_cluster.py                 # the chips this host has
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python examples/sort_cluster.py   # 8 virtual devices
"""
import jax
import numpy as np

import repro


def main():
    devices = jax.devices()
    p = len(devices)
    # a (data, model) mesh when the device count splits in two, else 1-D
    model = 2 if p % 2 == 0 and p > 2 else 1
    mesh = jax.make_mesh((p // model, model), ("data", "model"))
    print(f"devices: {p} ({devices[0].device_kind}); mesh {dict(mesh.shape)}")
    rng = np.random.default_rng(0)
    cfg = repro.SortConfig(capacity_factor=1.5)

    # sort 1M keys over the "data" axis — where=mesh pins
    # the mesh backend; everything else (plan, result type) is unchanged
    x = rng.normal(0, 1, 1 << 20).astype(np.float32)
    print(repro.explain(x, where=(mesh, "data"), config=cfg))
    r = repro.sort(x, where=(mesh, "data"), config=cfg)
    assert r.meta.backend == "mesh"
    assert (np.diff(r.keys) >= 0).all()
    print(f"{mesh.shape['data']}-proc distributed sort ok; per-proc counts {r.counts}")

    # multi-axis sort over ("data","model") = every device — the multi-pod
    # pattern (axis tuples work in every collective); descending + argsort
    # work here exactly as on every other backend
    keys = rng.integers(1, 6, 1 << 20).astype(np.int32)  # heavy duplication
    rkv = repro.sort(keys, np.arange(keys.size, dtype=np.int32),
                     where=(mesh, ("data", "model")), config=cfg)
    counts = np.asarray(rkv.counts)
    assert np.array_equal(keys[rkv.values], rkv.keys)
    print(f"{p}-proc kv sort under duplication: counts {counts} "
          f"(max/mean {counts.max()/counts.mean():.4f})")

    rd = repro.sort(keys, order="desc", where=(mesh, ("data", "model")), config=cfg)
    assert np.array_equal(rd.keys, np.sort(keys)[::-1])
    print("descending on the mesh backend: np-exact")


if __name__ == "__main__":
    main()
