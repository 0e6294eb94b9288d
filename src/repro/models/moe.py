"""Mixture-of-Experts with **zipper dispatch** — the paper's stream-sort
primitive as a first-class framework feature.

Token→expert routing is a key-value stream problem: keys = expert ids,
values = token slots. Dispatch = sort the stream by key (mssortk/mssortv
semantics, minus duplicate merging — tokens must be grouped, not summed),
then exchange grouped tokens across expert-parallel shards.

Two paths:

  zipper (production): shard_map over the mesh. Tokens are split over the
    model axis inside the MoE region (sequence parallelism), sorted by
    expert id with the zipper-sort primitive, packed into per-expert
    capacity bins, exchanged with a single all_to_all over the model axis
    (experts are model-sharded), run through batched expert FFNs, and
    combined back through the inverse permutation. Expert weights can be
    FSDP-sharded over the data axis and are all-gathered inside the region
    (ZeRO-3; the gather overlaps with routing on real hardware).

  einsum (reference): dense one-hot dispatch for tiny smoke configs and
    numerics cross-checks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd
from repro.kernels import ops as kops
from repro.models.layers import dense_init, mlp, mlp_init


def moe_init(key, cfg, dtype):
    E = cfg.num_experts
    D, F = cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 6)
    p = {
        "router": dense_init(ks[0], D, E, jnp.float32),
        "experts": {
            "w1": (jax.random.normal(ks[1], (E, D, F), jnp.float32) * D ** -0.5).astype(dtype),
            "w3": (jax.random.normal(ks[2], (E, D, F), jnp.float32) * D ** -0.5).astype(dtype),
            "w2": (jax.random.normal(ks[3], (E, F, D), jnp.float32) * F ** -0.5).astype(dtype),
        },
    }
    if cfg.num_shared_experts:
        p["shared"] = mlp_init(ks[4], D, F * cfg.num_shared_experts, dtype)
    if cfg.dense_residual:
        p["dense_mlp"] = mlp_init(ks[5], D, cfg.d_ff, dtype)
    return p


def _router(p, x, cfg):
    """x: (..., D) -> (topk ids (..., k), weights (..., k), logits)."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), p["router"]["w"])
    w, ids = jax.lax.top_k(logits, cfg.top_k)
    w = jax.nn.softmax(w, axis=-1)
    return ids.astype(jnp.int32), w, logits


def _expert_ffn(we, xe):
    """xe: (E_loc, C, D); we: dict of (E_loc, D, F)/(E_loc, F, D)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, we["w1"].astype(xe.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", xe, we["w3"].astype(xe.dtype))
    return jnp.einsum("ecf,efd->ecd", h, we["w2"].astype(xe.dtype))


def _capacity(T, k, E, cf):
    """Per-expert capacity. Small token counts (decode steps, smoke tests)
    get a dropless capacity so decode matches the full forward exactly."""
    if T * k <= 256:
        return T * k
    return -(-max(8, int(cf * T * k / E)) // 8) * 8


def _aux_loss(logits, ids, cfg):
    """Switch-style load-balance loss."""
    E = cfg.num_experts
    probs = jax.nn.softmax(logits, axis=-1).reshape(-1, E)
    hot = jax.nn.one_hot(ids.reshape(-1), E, dtype=jnp.float32)
    frac_tokens = hot.mean(0)
    frac_prob = probs.mean(0)
    return E * jnp.sum(frac_tokens * frac_prob)


# ---------------------------------------------------------------------------
# zipper dispatch
# ---------------------------------------------------------------------------

def moe_block(p, x, cfg, *, dispatch=None):
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar)."""
    dispatch = dispatch or cfg.moe_dispatch
    out_parts = []
    if cfg.dense_residual:
        out_parts.append(mlp(p["dense_mlp"], x, layout=cfg.layer_layout))
    if cfg.num_shared_experts:
        out_parts.append(mlp(p["shared"], x, layout=cfg.layer_layout))
    if dispatch == "einsum" or shd.get_mesh() is None:
        routed, aux = _einsum_moe(p, x, cfg)
    else:
        routed, aux = _shardmap_moe(p, x, cfg)
    out_parts.append(routed)
    return functools.reduce(jnp.add, out_parts), aux


def _einsum_moe(p, x, cfg):
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.top_k
    xt = x.reshape(-1, D)
    ids, w, logits = _router(p, xt, cfg)
    T = xt.shape[0]
    cap = _capacity(T, k, E, cfg.capacity_factor)
    # zipper-sort the (expert, slot) stream — paper primitive, XLA/Pallas path
    flat_ids = ids.reshape(-1)  # (T*k)
    _, perm = kops.sort_tokens_by_key(flat_ids, backend="xla")
    sorted_ids = flat_ids[perm]
    # position of each assignment within its expert group
    hot = jax.nn.one_hot(sorted_ids, E, dtype=jnp.int32)
    pos_sorted = (jnp.cumsum(hot, axis=0) - hot)[jnp.arange(T * k), sorted_ids]
    pos = jnp.zeros(T * k, jnp.int32).at[perm].set(pos_sorted)
    keep = pos < cap
    buf = jnp.zeros((E, cap, D), x.dtype)
    tok = jnp.repeat(jnp.arange(T), k)
    buf = buf.at[flat_ids, jnp.where(keep, pos, 0)].add(
        jnp.where(keep[:, None], xt[tok], 0))
    ye = _expert_ffn(p["experts"], buf)
    yt = ye[flat_ids, jnp.where(keep, pos, 0)]
    yt = jnp.where(keep[:, None], yt, 0) * w.reshape(-1)[:, None].astype(x.dtype)
    out = jnp.zeros_like(xt).at[tok].add(yt)
    return out.reshape(B, S, D), _aux_loss(logits, ids, cfg)


def _shardmap_moe(p, x, cfg):
    """Production path: shard_map(zipper sort + all_to_all EP)."""
    mesh = shd.get_mesh()
    ba = shd.batch_axes()
    n_model = shd.model_axis_size()
    E = cfg.num_experts
    B, S, D = x.shape
    k = cfg.top_k
    fsdp = cfg.fsdp and "data" in mesh.axis_names
    # sequence-shard tokens over the model axis when the shape allows it
    # (training/prefill); decode (S < n_model) replicates routing over the
    # model axis — expert FFNs stay sharded either way.
    seq_shard = S % n_model == 0 and S >= n_model
    s_div = n_model if seq_shard else 1
    b_div = max(1, shd.data_axis_size()) if B % max(1, shd.data_axis_size()) == 0 else 1

    T_loc = (B // b_div) * (S // s_div)
    cap = _capacity(T_loc, k, E, cfg.capacity_factor)
    E_loc = E // n_model

    we = p["experts"]
    w_spec = P("model", "data", None) if fsdp else P("model", None, None)
    w2_spec = P("model", None, "data") if fsdp else P("model", None, None)

    def body(wr, w1, w3, w2, xl):
        # xl: (B_loc, S_loc, D); w1/w3: (E_loc, D[/dp], F); wr: (D, E)
        if fsdp:
            w1 = jax.lax.all_gather(w1, "data", axis=1, tiled=True)
            w3 = jax.lax.all_gather(w3, "data", axis=1, tiled=True)
            w2 = jax.lax.all_gather(w2, "data", axis=2, tiled=True)
        bl, sl, _ = xl.shape
        xt = xl.reshape(-1, D)
        T = xt.shape[0]
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), wr)
        wk, ids = jax.lax.top_k(logits, k)
        wk = jax.nn.softmax(wk, axis=-1)
        flat_ids = ids.reshape(-1).astype(jnp.int32)
        # ---- zipper sort (mssortk/mssortv semantics, group-not-merge) ----
        _, perm = kops.sort_tokens_by_key(flat_ids, backend="xla")
        sorted_ids = flat_ids[perm]
        hot = jax.nn.one_hot(sorted_ids, E, dtype=jnp.int32)
        pos_sorted = (jnp.cumsum(hot, axis=0) - hot)[
            jnp.arange(T * k), sorted_ids]
        keep = pos_sorted < cap
        tok_sorted = perm // k
        buf = jnp.zeros((E, cap, D), xl.dtype)
        buf = buf.at[sorted_ids, jnp.where(keep, pos_sorted, 0)].add(
            jnp.where(keep[:, None], xt[tok_sorted], 0))
        # ---- EP exchange: (E, cap, D) -> (E_loc, n_model * cap, D) ----
        xe = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                                tiled=True)
        ye = _expert_ffn({"w1": w1, "w3": w3, "w2": w2}, xe)
        # ---- reverse exchange (exact inverse of the tiled all_to_all) ----
        ye = jax.lax.all_to_all(ye, "model", split_axis=1, concat_axis=0,
                                tiled=True)
        y_sorted = ye[sorted_ids, jnp.where(keep, pos_sorted, 0)]
        y_sorted = jnp.where(keep[:, None], y_sorted, 0)
        # ---- combine: inverse zipper permutation + top-k weighting ----
        y_flat = jnp.zeros((T * k, D), xl.dtype).at[perm].set(y_sorted)
        y = (y_flat.reshape(T, k, D) *
             wk[..., None].astype(xl.dtype)).sum(1)
        # aux loss (local estimate; mean over data axes happens in caller)
        probs = jax.nn.softmax(logits, axis=-1)
        frac_t = jax.nn.one_hot(ids.reshape(-1), E, dtype=jnp.float32).mean(0)
        aux = E * jnp.sum(frac_t * probs.mean(0))
        aux = jax.lax.pmean(aux, "model")
        for a in ba:
            aux = jax.lax.pmean(aux, a)
        return y.reshape(bl, sl, D), aux

    x_spec = P(ba if (ba and B % max(1, shd.data_axis_size()) == 0) else None,
               "model" if seq_shard else None, None)
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), w_spec, w_spec, w2_spec, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(p["router"]["w"], we["w1"], we["w3"], we["w2"], x)
    return y, aux
