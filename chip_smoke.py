"""Smoke run of the sort's main path on a TPU, through the user entry points.

    python chip_smoke.py [--seed N]            # one chip
    python chip_smoke.py --chips 4 [--seed N]  # the mesh sort on four chips

One chip runs three phases with the library defaults (``SortConfig()``,
Pallas kernels on):

  a. in-core: ``repro.sort(keys, values)`` on 2^26 int32 keys of the
     paper's duplicate-heavy ``right_skewed`` distribution with int32
     payloads, then a default-limits 2^22 ``order="desc", want="order"``
     argsort (device decode);
  b. out-of-core: 2^27 int32 keys through the default planner (stream);
  c. serving: a ``SortServer`` answering 48 requests from 6 threads —
     coalesced keys-only sorts, kv, argsort and ``submit_topk``.

``--chips 4`` runs only ``repro.sort(k, v, where=(mesh, "data"))`` with
2^25 keys and payloads per chip, and the same data sorted on one chip.

Every phase prints one JSON line: sizes, the backend the planner chose,
wall-clock seconds (a smoke timing, not a benchmark), the device's peak
bytes in use so far, whether the output equals numpy exactly, and whether
the compiled sort program holds a Pallas kernel (``tpu_custom_call``).
The last line names the device. The script exits non-zero, without that
line, when JAX finds no TPU or any phase fails.

Data comes from ``--seed``. JAX's compile cache goes to
``$JAX_COMPILATION_CACHE_DIR`` when set, else to ``.jax_cache/`` next to
this file.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro  # noqa: E402
from benchmarks.common import distribution, use_compile_cache  # noqa: E402
from repro.core import sim  # noqa: E402
from repro.serve import SortServer  # noqa: E402

IN_CORE = 1 << 26
ARGSORT = 1 << 22
OUT_OF_CORE = 1 << 27
MESH_PER_CHIP = 1 << 25
SERVE_THREADS = 6
SERVE_PER_THREAD = 8
SERVE_SIZES = (512, 4096, 1 << 16)


def right_skewed(rng, n: int) -> jax.Array:
    """The paper's Fig. 4 right-skewed input (64 distinct values, most of
    them near 0), as int32 keys on the device."""
    return distribution("right_skewed", rng, 1, n).reshape(-1).astype(jnp.int32)


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def report(phase: str, t0: float, exact: bool, **fields) -> None:
    line = {"phase": phase, **fields,
            "wall_s_smoke_not_benchmark": time.perf_counter() - t0,
            "peak_bytes_in_use": peak_bytes(), "np_exact": bool(exact)}
    print(json.dumps(line), flush=True)
    if not exact:
        raise AssertionError(f"phase {phase}: output differs from numpy")


def sim_program_has_kernel(out, n: int, kv: bool) -> bool:
    """Compile the sim program the planner ran for ``out`` (the persistent
    cache makes this a lookup) and look for a Pallas kernel in it."""
    p = out.meta.plan.n_procs
    spec = jax.ShapeDtypeStruct((p, n // p), jnp.int32)
    if kv:
        lowered = sim.sample_sort_sim_kv.lower(spec, spec, config=out.meta.config,
                                               investigator=True)
    else:
        lowered = sim.sample_sort_sim.lower(spec, config=out.meta.config,
                                            investigator=True)
    return "tpu_custom_call" in lowered.compile().as_text()


def kv_exact(keys_in: np.ndarray, out) -> bool:
    """Sorted keys equal np.sort, and the payload (each key's input
    position) is a permutation whose every entry points at its own key.
    Checked in O(n): a non-decreasing ``keys_in[perm]`` is np.sort."""
    ks, vs = np.asarray(out.keys), np.asarray(out.values)
    n = keys_in.size
    if ks.shape != (n,) or vs.shape != (n,) or vs.min() < 0 or vs.max() >= n:
        return False
    seen = np.zeros(n, bool)
    seen[vs] = True
    return bool(seen.all() and np.array_equal(keys_in[vs], ks)
                and (ks[1:] >= ks[:-1]).all())


def phase_in_core(rng, n: int = IN_CORE, n_order: int = ARGSORT) -> None:
    keys = right_skewed(rng, n)
    ids = jnp.arange(n, dtype=jnp.int32)
    jax.block_until_ready((keys, ids))
    t0 = time.perf_counter()
    out = repro.sort(keys, ids, limits=repro.SortLimits(stream_threshold=None))
    _ = out.keys, out.values  # the D2H copy ends the timed sort
    t_sort = time.perf_counter() - t0
    exact = kv_exact(np.asarray(keys), out)
    report("a_in_core_kv", t0, exact, n=n, dtype="int32", payload="int32",
           distribution="right_skewed", backend=out.meta.backend,
           sort_and_d2h_wall_s=t_sort,
           tpu_custom_call=sim_program_has_kernel(out, n, kv=True))

    keys = right_skewed(rng, n_order)
    jax.block_until_ready(keys)
    t0 = time.perf_counter()
    out = repro.sort(keys, order="desc", want="order")
    order = out.order()
    host = np.asarray(keys)
    exact = (np.array_equal(order, np.argsort(-host.astype(np.int64), kind="stable"))
             and np.array_equal(out.keys, host[order]))
    report("a_in_core_desc_argsort", t0, exact, n=n_order, dtype="int32",
           backend=out.meta.backend, decode=out.meta.plan.decode)


def phase_out_of_core(rng, n: int = OUT_OF_CORE) -> None:
    keys = np.asarray(right_skewed(rng, n))
    t0 = time.perf_counter()
    out = repro.sort(keys)
    got = out.keys
    exact = np.array_equal(got, np.sort(keys))
    report("b_out_of_core", t0, exact, n=n, dtype="int32", backend=out.meta.backend,
           chunk_elems=out.meta.plan.chunk_elems)


def phase_serving(rng, threads: int = SERVE_THREADS,
                  per_thread: int = SERVE_PER_THREAD, sizes=SERVE_SIZES) -> None:
    """Each thread submits a mix of request kinds, then checks every
    answer against numpy."""
    kinds = ("sort", "kv", "argsort", "topk")
    work = []
    for t in range(threads):
        for j in range(per_thread):
            n = sizes[(t + j) % len(sizes)]
            keys = rng.integers(-1000, 1000, n).astype(np.int32)
            work.append((t, kinds[j % len(kinds)], keys))
    failures: list[str] = []
    meta = {"coalesced": 0, "backends": set()}
    lock = threading.Lock()

    def client(srv, t: int) -> None:
        mine = [(kind, k) for tt, kind, k in work if tt == t]
        futs = []
        for kind, k in mine:
            if kind == "sort":
                futs.append(srv.submit(k))
            elif kind == "kv":
                futs.append(srv.submit(k, np.arange(k.size, dtype=np.int32)))
            elif kind == "argsort":
                futs.append(srv.submit(k, want="order"))
            else:
                futs.append(srv.submit_topk(k, 10))
        for (kind, k), fut in zip(mine, futs):
            out = fut.result(timeout=600)
            if kind == "sort":
                ok = np.array_equal(out.keys, np.sort(k))
            elif kind == "kv":
                ok = kv_exact(k, out)
            elif kind == "argsort":
                ok = np.array_equal(out.order(), np.argsort(k, kind="stable"))
            else:
                ok = np.array_equal(out.keys, np.sort(k)[::-1][:10])
            with lock:
                meta["backends"].add(out.meta.backend)
                meta["coalesced"] += bool(out.meta.coalesced)
                if not ok:
                    failures.append(f"{kind} n={k.size}")

    t0 = time.perf_counter()
    errors: list[BaseException] = []

    def run(srv, t):
        try:
            client(srv, t)
        except BaseException as e:  # re-raised below, on the main thread
            errors.append(e)

    with SortServer(max_batch=16, max_delay_ms=5.0) as srv:
        pool = [threading.Thread(target=run, args=(srv, t)) for t in range(threads)]
        for th in pool:
            th.start()
        for th in pool:
            th.join()
        stats = srv.stats()
    if errors:
        raise errors[0]
    report("c_serving", t0, not failures, requests=len(work), threads=threads,
           sizes=list(sizes), kinds=list(kinds), backends=sorted(meta["backends"]),
           coalesced_results=meta["coalesced"], flushes=stats.get("flushes"),
           failures=failures)


def phase_mesh(rng, chips: int, per_chip: int = MESH_PER_CHIP) -> None:
    n = chips * per_chip
    mesh = jax.make_mesh((chips,), ("data",))
    keys = right_skewed(rng, n)
    ids = jnp.arange(n, dtype=jnp.int32)
    jax.block_until_ready((keys, ids))
    host = np.asarray(keys)

    t0 = time.perf_counter()
    out = repro.sort(keys, ids, where=(mesh, "data"))
    exact = kv_exact(host, out)
    counts = np.asarray(out.counts)
    report("mesh_kv", t0, exact and bool((counts > 0).all()), n=n, chips=chips,
           backend=out.meta.backend, counts=counts.tolist(),
           imbalance_max_over_mean=float(counts.max() / counts.mean()),
           devices=[str(d) for d in mesh.devices.flat])

    del out  # the one-chip sort below needs most of device 0's memory
    t0 = time.perf_counter()
    one = repro.sort(keys, ids, limits=repro.SortLimits(stream_threshold=None))
    report("one_chip_kv", t0, kv_exact(host, one), n=n,
           backend=one.meta.backend)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {len(devices)}",
              file=sys.stderr)
        return 2
    use_compile_cache()
    rng = np.random.default_rng(args.seed)
    if args.chips == 1:
        phase_in_core(rng)
        phase_out_of_core(rng)
        phase_serving(rng)
    else:
        phase_mesh(rng, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
