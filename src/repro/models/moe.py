"""Mixture-of-Experts with *sort-based dispatch* — the paper's technique
as a first-class framework feature (DESIGN.md §3).

Token routing **is** a distributed sort keyed by expert id: expert ids
have only E distinct values, i.e. maximal key duplication — exactly the
load-balance regime the paper's investigator targets. The dispatch below
is the paper's six-step pipeline transplanted per MoE layer:

  (1) local stable sort of (expert_id, slot) pairs          [core/local_sort]
  (2-4) destination bounds: expert->shard map is static, so the
        "splitters" are the shard-first expert ids; capacity clipping
        plays the investigator's role of bounding any destination's load
  (5) one fused static-capacity all_to_all over the expert axes
  (6) receive-side grouping via the balanced pairwise merge tree
        [core/merge.merge_padded_runs_kv — paper Fig. 2]

Expert sharding: 1-D over ("model",) by default; 2-D over
("data","model") when the expert count divides the full slice (deepseek-
v3: 256 experts -> 1 expert/device on a 16x16 pod). Tokens enter sharded
(batch over data/pod, sequence over model) so routing work is also
perfectly balanced before dispatch.

The same body runs without any mesh (axes=None, n_shards=1, identity
exchange) for single-device smoke tests, and ``moe_ref`` is the dense
one-hot oracle used by the unit tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import keyenc
from repro.core.merge import merge_padded_runs_kv
from repro.models.layers import _init, _act
from repro.sharding.spec import Axes


def init_moe(key, cfg, axes, stack=()):
    dtype = jnp.dtype(cfg.dtype)
    d, de, E = cfg.d_model, cfg.d_expert, cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": _init(ks[0], stack + (d, E), d ** -0.5, jnp.float32),
        "wi": _init(ks[1], stack + (E, d, de), d ** -0.5, dtype),
        "wg": _init(ks[2], stack + (E, d, de), d ** -0.5, dtype),
        "wo": _init(ks[3], stack + (E, de, d), de ** -0.5, dtype),
    }
    return p


def _router(xf, router_w, cfg):
    """Softmax-topk routing with renormalized weights + switch aux loss."""
    logits = xf.astype(jnp.float32) @ router_w  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, cfg.moe_topk)  # (T, K)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # load-balance aux (Switch): E * sum_e f_e * P_e
    E = router_w.shape[-1]
    f = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    f = f / jnp.maximum(f.sum(), 1.0)
    pbar = probs.mean(0)
    aux = E * jnp.sum(f * pbar)
    return w, ids.astype(jnp.int32), aux


def _expert_ffn(xe, p, cfg):
    """xe: (E_loc, cap, d) -> (E_loc, cap, d). Batched per-expert SwiGLU."""
    h = jnp.einsum("ecd,edf->ecf", xe, p["wi"])
    g = jnp.einsum("ecd,edf->ecf", xe, p["wg"])
    h = _act(g, cfg.act) * h
    return jnp.einsum("ecf,efd->ecd", h, p["wo"])


def _dispatch_body(
    xf, p, cfg, *, n_shards: int, shard_id, a2a, use_pallas: bool = False,
    tp_axis: str | None = None,
):
    """Per-device dispatch pipeline (the paper's 6 steps). xf: (T, d)."""
    T, d = xf.shape
    E = cfg.n_experts
    K = cfg.moe_topk
    E_loc = E // n_shards
    A = T * K  # local assignments

    w, ids, aux = _router(xf, p["router"], cfg)

    # ---- (1) local stable argsort of expert ids — paper step 1, via the
    # front end's key-encoding layer (slot payload = the stable argsort)
    keys = ids.reshape(-1)  # (A,)
    skeys, sslots = keyenc.stable_argsort(keys, use_pallas=use_pallas)

    # ---- (2-4) static splitters = first expert of each shard
    shard_first = jnp.arange(n_shards + 1, dtype=jnp.int32) * E_loc
    bounds = jnp.searchsorted(skeys, shard_first, side="left").astype(jnp.int32)
    send_counts = bounds[1:] - bounds[:-1]  # (n_shards,)
    C = max(1, int((A + n_shards - 1) // n_shards * cfg.moe_capacity_factor) + 1)

    # ---- (5) bucketize + fused all_to_all (keys + token vectors)
    pos = jnp.arange(C, dtype=jnp.int32)
    starts = bounds[:-1]
    idx = starts[:, None] + pos[None, :]  # (n_shards, C)
    valid = pos[None, :] < send_counts[:, None]
    idx_c = jnp.minimum(idx, A - 1)
    bkeys = jnp.where(valid, skeys[idx_c], E)  # sentinel = E (max)
    bslots = jnp.where(valid, sslots[idx_c], A)
    btok = jnp.where(valid[..., None], xf[jnp.minimum(bslots, A - 1) // K], 0)
    rkeys = a2a(bkeys)  # (n_shards, C)
    rtok = a2a(btok)  # (n_shards, C, d)

    # ---- (6) group by local expert: balanced pairwise merge (Fig. 2)
    pool_idx = jnp.arange(n_shards * C, dtype=jnp.int32).reshape(n_shards, C)
    mkeys, mpool = merge_padded_runs_kv(rkeys, pool_idx, use_pallas=use_pallas)
    pool = rtok.reshape(n_shards * C, d)

    # per-expert segments + capacity (the investigator's balance bound)
    first = shard_id * E_loc
    e_bounds = jnp.searchsorted(
        mkeys, first + jnp.arange(E_loc + 1, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    cap_e = max(1, int(T * K * n_shards // max(E, 1) * cfg.moe_capacity_factor) + 1)
    epos = jnp.arange(cap_e, dtype=jnp.int32)
    eidx = e_bounds[:-1, None] + epos[None, :]  # (E_loc, cap_e)
    evalid = eidx < e_bounds[1:, None]
    rows = jnp.where(evalid, mpool[jnp.minimum(eidx, n_shards * C - 1)], n_shards * C)
    xe = pool.at[jnp.minimum(rows, n_shards * C - 1)].get() * evalid[..., None]

    # ---- expert FFN (d_expert may be TP-sharded: psum the contraction)
    ye = _expert_ffn(xe.astype(xf.dtype), p, cfg)
    if tp_axis is not None:
        ye = jax.lax.psum(ye, tp_axis)

    # ---- route back: scatter to pool rows, inverse all_to_all
    out_pool = jnp.zeros((n_shards * C, d), xf.dtype)
    out_pool = out_pool.at[rows.reshape(-1)].set(
        (ye * evalid[..., None]).reshape(-1, d), mode="drop"
    )
    back = a2a(out_pool.reshape(n_shards, C, d))  # source-bucket layout

    # ---- scatter to slots, combine top-k
    out_flat = jnp.zeros((A, d), xf.dtype)
    tgt = jnp.where(valid, jnp.minimum(bslots, A - 1), A)
    out_flat = out_flat.at[tgt.reshape(-1)].set(back.reshape(-1, d), mode="drop")
    out = (out_flat.reshape(T, K, d) * w[..., None].astype(xf.dtype)).sum(1)
    return out, aux, send_counts


def _make_a2a(axis_names, hierarchical: bool = False):
    """Bucket exchange over the expert axes.

    ``hierarchical=True`` (§Perf iteration on the 2-D EP dispatch): the
    tuple-axis all_to_all over ("data","model") addresses non-contiguous
    device groups and lowers poorly (XLA emits all-gathers); the same
    permutation decomposes into two single-axis exchanges —

        r[(d1,d2)][(s1,s2)] = x[(s1,s2)][(d1,d2)]
          == a2a_axis1(a2a_axis0(x.reshape(S1, S2, C)))

    — each over contiguous groups, with identical total bytes."""
    if hierarchical and isinstance(axis_names, (tuple, list)) and len(axis_names) == 2:
        a1, a2 = axis_names

        def a2a(x):
            s1 = jax.lax.axis_size(a1)
            s2 = jax.lax.axis_size(a2)
            y = x.reshape((s1, s2) + x.shape[1:])
            y = jax.lax.all_to_all(y, a1, split_axis=0, concat_axis=0, tiled=True)
            y = jax.lax.all_to_all(y, a2, split_axis=1, concat_axis=1, tiled=True)
            return y.reshape((s1 * s2,) + x.shape[1:])

        return a2a

    def a2a(x):
        return jax.lax.all_to_all(x, axis_names, split_axis=0, concat_axis=0, tiled=True)

    return a2a


def _shard_index(axis_names) -> jnp.ndarray:
    idx = jnp.int32(0)
    for a in axis_names:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def moe_forward(x, p, cfg, axes: Axes | None, *, use_pallas: bool = False,
                tp_axis: str | None = None):
    """x: (B, S, d) [batch sharded over axes.batch, replicated over model].
    Returns (out (B,S,d), aux scalar).

    ``tp_axis``: additionally tensor-parallel-shard d_expert over that mesh
    axis (EP x TP — the decode-mode sharding for very large expert counts:
    deepseek-v3 decodes with experts over "data" and d_expert over "model",
    see DESIGN.md §5). The body then psums the wo contraction over tp_axis.
    """
    B, S, d = x.shape

    if axes is None or axes.expert_size == 1:
        xf = x.reshape(-1, d)
        out, aux, _ = _dispatch_body(
            xf, p, cfg, n_shards=1, shard_id=jnp.int32(0), a2a=lambda t: t,
            use_pallas=use_pallas,
        )
        return out.reshape(B, S, d), aux

    from repro.sharding.rules import fit_batch_axes

    enames = axes.expert
    n_shards = axes.expert_size
    # inside a traced step the ambient mesh (whose axis types the caller
    # may have switched, e.g. by jax.sharding.auto_axes) wins
    ambient = jax.sharding.get_abstract_mesh()
    mesh = axes.mesh if ambient.empty else ambient
    bax = fit_batch_axes(B, axes)
    # shard the sequence over "model" when possible (token-parallel
    # routing); decode (S == 1) replicates over model instead.
    sax = axes.model if (S % axes.model_size == 0 and tp_axis is None) else None

    def body(xl, pl):
        Bl, Sl, _ = xl.shape
        out, aux, _ = _dispatch_body(
            xl.reshape(-1, d), pl, cfg,
            n_shards=n_shards,
            shard_id=_shard_index(enames),
            a2a=_make_a2a(enames, hierarchical=getattr(cfg, "hierarchical_a2a", False)),
            use_pallas=use_pallas,
            tp_axis=tp_axis,
        )
        # aux: average over all participating devices -> replicated scalar
        aux = jax.lax.pmean(aux, mesh.axis_names)
        return out.reshape(Bl, Sl, d), aux

    de_ax = tp_axis  # d_expert TP sharding (None in the pure-EP regime)
    pspec = {
        "router": P(),
        "wi": P(axes.expert, None, de_ax),
        "wg": P(axes.expert, None, de_ax),
        "wo": P(axes.expert, de_ax, None),
    }
    f = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(bax, sax, None), pspec),
        out_specs=(P(bax, sax, None), P()),
        check_vma=False,
    )
    return f(x, p)


def moe_forward_decode(x, p, cfg, axes: Axes | None):
    """Decode-time MoE (S == 1): too few tokens to shard over the expert
    axes, so serving uses *expert tensor parallelism* instead — expert
    weights sharded on d_expert over "model" (the serve-mode sharding rule)
    and each token gathers exactly its top-k experts' weight slices. FLOPs
    equal the active-expert compute; the HBM traffic (reading the selected
    expert slices) is the intrinsic MoE decode cost. GSPMD inserts the
    all-reduce over the contracted d_expert shards."""
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    w, ids, aux = _router(xf, p["router"], cfg)
    wi = jnp.take(p["wi"], ids, axis=0)  # (T,K,d,de)
    wg = jnp.take(p["wg"], ids, axis=0)
    wo = jnp.take(p["wo"], ids, axis=0)  # (T,K,de,d)
    h = jnp.einsum("td,tkdf->tkf", xf, wi)
    g = jnp.einsum("td,tkdf->tkf", xf, wg)
    y = jnp.einsum("tkf,tkfd->tkd", _act(g, cfg.act) * h, wo)
    out = (y * w[..., None].astype(xf.dtype)).sum(1)
    return out.reshape(B, S, d), aux


# ------------------------------------------------------------------ oracle


def moe_ref(x, p, cfg):
    """Dense one-hot reference (no capacity drops) for unit tests."""
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    w, ids, aux = _router(xf, p["router"], cfg)
    onehot = jax.nn.one_hot(ids, cfg.n_experts, dtype=xf.dtype)  # (T,K,E)
    combine = (onehot * w[..., None].astype(xf.dtype)).sum(1)  # (T,E)
    h = jnp.einsum("td,edf->tef", xf, p["wi"])
    g = jnp.einsum("td,edf->tef", xf, p["wg"])
    y = jnp.einsum("tef,efd->ted", _act(g, cfg.act) * h, p["wo"])
    out = (y * combine[..., None]).sum(1)
    return out.reshape(B, S, d), aux
