"""Top-k routed MoE with capacity-bounded scatter dispatch.

Design notes (TPU adaptation):

* Expert weights are sharded over the "model" mesh axis (expert parallelism);
  token activations are sharded over ("pod", "data").  The token->expert
  re-layout is expressed as a scatter into an [E, C, D] buffer with sharding
  constraints; GSPMD lowers the cross-shard movement to all-to-all /
  collective-permute (inspected in the dry-run HLO).
* We deliberately do NOT use GShard einsum dispatch: with E=128 experts the
  [N, E, C] dispatch einsum costs E*C/k (~600x) more FLOPs than the useful
  work.  Scatter/gather keeps HLO FLOPs equal to routed-token matmul FLOPs,
  which is what the §Roofline "useful ratio" is measured against.
* Capacity factor bounds the per-expert buffer; overflowing tokens are
  dropped (standard Switch/GShard semantics) and their residual passes
  through unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.sharding import with_logical_constraint as wlc


def init_moe(key, cfg: ModelConfig, param_dtype) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    p = {
        "router": L.dense_init(k1, (d, e), ("embed", "unsharded"), param_dtype,
                               fan_in=d),
        "wi_gate": L.dense_init(k2, (e, d, f), ("expert", "embed", "expert_mlp"),
                                param_dtype, fan_in=d),
        "wi_up": L.dense_init(k3, (e, d, f), ("expert", "embed", "expert_mlp"),
                              param_dtype, fan_in=d),
        "wo": L.dense_init(k4, (e, f, d), ("expert", "expert_mlp", "embed"),
                           param_dtype, fan_in=f),
    }
    if cfg.num_shared_experts:
        fs = f * cfg.num_shared_experts
        p["shared"] = L.init_mlp(k5, d, fs, param_dtype)
    return p


def _capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(n_tokens * cfg.num_experts_per_token * cfg.moe_capacity_factor
            / cfg.num_experts)
    # round up to a lane-friendly multiple
    return max(8, -(-c // 8) * 8)


def moe_apply(p: dict, cfg: ModelConfig, x: jax.Array):
    """x [B, S, D] -> (out [B, S, D], aux_loss scalar).

    Dispatches to the explicit all-to-all implementation when
    ``cfg.moe_impl == "shard_map"`` and a mesh with a "model" axis is
    active; otherwise the GSPMD scatter path below.
    """
    if getattr(cfg, "moe_impl", "gspmd") == "shard_map":
        from repro.sharding.partition import current_mesh_and_rules
        ctx = current_mesh_and_rules()
        if ctx is not None and "model" in ctx[0].axis_names \
                and cfg.num_experts % ctx[0].shape["model"] == 0:
            return moe_apply_shard_map(p, cfg, x, ctx[0])
    return moe_apply_gspmd(p, cfg, x)


def moe_apply_gspmd(p: dict, cfg: ModelConfig, x: jax.Array):
    """x [B, S, D] -> (out [B, S, D], aux_loss scalar)."""
    dt = x.dtype
    b, s, d = x.shape
    n = b * s
    e, k = cfg.num_experts, cfg.num_experts_per_token
    cap = _capacity(cfg, n)

    xf = x.reshape(n, d)
    logits = jnp.einsum("nd,de->ne", xf, p["router"].astype(dt))
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)

    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # [N, k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # ---- load-balancing aux loss (Switch eq. 4) ----
    me = jnp.mean(probs, axis=0)  # [E]
    one_hot_top1 = jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32)
    fe = jnp.mean(one_hot_top1, axis=0)
    aux_loss = cfg.router_aux_loss_coef * e * jnp.sum(me * fe)

    # ---- slot assignment: position of each (token, choice) in its expert ----
    # Sort-based ranking (MegaBlocks-style) instead of a [N*k, E] one-hot
    # cumsum: XLA lowers big cumsums to reduce-window with O(len^2) counted
    # cost, which poisons both the roofline FLOPs and the partitioner.  A
    # stable argsort keeps Switch "first tokens win" capacity semantics.
    flat_e = expert_idx.reshape(n * k)  # row-major: all k choices of token 0
    order = jnp.argsort(flat_e, stable=True)  # [A]
    sorted_e = jnp.take(flat_e, order)
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos_sorted = jnp.arange(n * k, dtype=jnp.int32) - jnp.take(starts, sorted_e)
    pos = jnp.zeros((n * k,), jnp.int32).at[order].set(pos_sorted)
    keep = pos < cap
    slot = jnp.where(keep, flat_e * cap + pos, e * cap)  # overflow -> dump row

    # ---- dispatch: scatter token embeddings into [E*C(+1 dump), D] ----
    x_rep = jnp.repeat(xf, k, axis=0)  # [N*k, D]
    buf = jnp.zeros((e * cap + 1, d), dtype=dt).at[slot].set(x_rep)
    buf = buf[: e * cap].reshape(e, cap, d)
    # 2D expert sharding: experts over "model" (EP) AND capacity over
    # "data" — without the capacity split, the [E_loc, cap_global, D]
    # buffer replicates across the data axis and every data shard
    # duplicates the expert matmuls (16x waste observed in the dry-run HLO).
    buf = wlc(buf, ("expert", "expert_cap", None))

    # ---- expert FFN (SwiGLU), E sharded over "model" ----
    gate = jnp.einsum("ecd,edf->ecf", buf, p["wi_gate"].astype(dt))
    up = jnp.einsum("ecd,edf->ecf", buf, p["wi_up"].astype(dt))
    h = jax.nn.silu(gate) * up
    h = wlc(h, ("expert", "expert_cap", "expert_mlp"))
    y = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(dt))
    y = wlc(y, ("expert", "expert_cap", None))

    # ---- combine: gather back, weight, sum over k choices ----
    y_flat = jnp.concatenate(
        [y.reshape(e * cap, d), jnp.zeros((1, d), dtype=dt)], axis=0)
    gathered = y_flat[slot]  # [N*k, D]
    w = (gate_vals.reshape(n * k, 1) * keep[:, None]).astype(dt)
    out = jnp.sum((gathered * w).reshape(n, k, d), axis=1)

    if cfg.num_shared_experts:
        out = out + L.mlp_apply(p["shared"], x).reshape(n, d)

    out = out.reshape(b, s, d)
    out = wlc(out, ("batch", None, None))
    return out, aux_loss


# ---------------------------------------------------------------------------
# Explicit expert parallelism (shard_map + all_to_all) — §Perf iteration 2
# ---------------------------------------------------------------------------

def _pack_by_bucket(bucket: jax.Array, n_buckets: int, cap: int,
                    rows: jax.Array, extra: jax.Array):
    """Pack ``rows`` [A, D] into [n_buckets*cap, D] by bucket id (stable,
    first-come capacity).  ``extra`` [A, m] int32 rides along (dropped rows
    get sentinel -1).  Returns (packed_rows, packed_extra, slot_of_row,
    keep_mask)."""
    a = bucket.shape[0]
    order = jnp.argsort(bucket, stable=True)
    sorted_b = jnp.take(bucket, order)
    counts = jnp.zeros((n_buckets,), jnp.int32).at[bucket].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    pos_sorted = jnp.arange(a, dtype=jnp.int32) - jnp.take(starts, sorted_b)
    pos = jnp.zeros((a,), jnp.int32).at[order].set(pos_sorted)
    keep = pos < cap
    slot = jnp.where(keep, bucket * cap + pos, n_buckets * cap)
    packed = jnp.zeros((n_buckets * cap + 1, rows.shape[1]),
                       rows.dtype).at[slot].set(rows)[:-1]
    pext = jnp.full((n_buckets * cap + 1, extra.shape[1]), -1,
                    jnp.int32).at[slot].set(
        jnp.where(keep[:, None], extra, -1))[:-1]
    return packed, pext, slot, keep


def moe_apply_shard_map(p: dict, cfg: ModelConfig, x: jax.Array, mesh):
    """Production EP: tokens resharded over "model", routed assignments
    exchanged with two all-to-alls (dispatch + combine), experts computed
    on their owning shard only.

    Wire volume per direction ~= routed-token bytes / devices — the
    GSPMD-scatter baseline instead all-gathers the routed activations.
    """
    from jax.sharding import PartitionSpec as P

    dt = x.dtype
    b, s, d = x.shape
    n = b * s
    e, k = cfg.num_experts, cfg.num_experts_per_token
    m_size = mesh.shape["model"]
    e_loc = e // m_size
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_shards = m_size
    for a_ in batch_axes:
        n_shards *= mesh.shape[a_]
    if n % n_shards:
        return moe_apply_gspmd(p, cfg, x)

    xf = x.reshape(n, d)
    logits = jnp.einsum("nd,de->ne", xf, p["router"].astype(dt))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    me = jnp.mean(probs, axis=0)
    fe = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32), 0)
    aux_loss = cfg.router_aux_loss_coef * e * jnp.sum(me * fe)

    n_loc = n // n_shards
    a_loc = n_loc * k
    send_cf = getattr(cfg, "moe_send_capacity_factor", 1.5)
    cap_send = max(8, -(- int(a_loc / m_size * send_cf) // 8) * 8)
    cap_loc = max(8, -(- int(cap_send * m_size / e_loc
                             * cfg.moe_capacity_factor) // 8) * 8)

    tok_spec = P(batch_axes + ("model",), None)

    def local_moe(x_loc, idx_loc, gates_loc, wg, wu, wo):
        # x_loc [n_loc, D]; idx/gates [n_loc, k]; w* [E_loc, ...]
        flat_e = idx_loc.reshape(a_loc)
        dest = flat_e // e_loc
        le = (flat_e % e_loc).astype(jnp.int32)
        x_rep = jnp.repeat(x_loc, k, axis=0)
        meta = jnp.stack([le, jnp.arange(a_loc, dtype=jnp.int32)], axis=1)
        send, send_meta, slot, keep = _pack_by_bucket(
            dest.astype(jnp.int32), m_size, cap_send, x_rep, meta)

        recv = jax.lax.all_to_all(send, "model", split_axis=0,
                                  concat_axis=0, tiled=True)
        recv_meta = jax.lax.all_to_all(send_meta, "model", split_axis=0,
                                       concat_axis=0, tiled=True)

        r = recv.shape[0]
        le_r = jnp.where(recv_meta[:, 0] >= 0, recv_meta[:, 0], e_loc)
        buf, _, slot_r, keep_r = _pack_by_bucket(
            le_r.astype(jnp.int32), e_loc + 1, cap_loc, recv,
            jnp.zeros((r, 1), jnp.int32))
        buf = buf.reshape(e_loc + 1, cap_loc, d)[:e_loc]

        gate = jnp.einsum("ecd,edf->ecf", buf, wg.astype(dt))
        up = jnp.einsum("ecd,edf->ecf", buf, wu.astype(dt))
        h = jax.nn.silu(gate) * up
        y = jnp.einsum("ecf,efd->ecd", h, wo.astype(dt))

        y_flat = jnp.concatenate(
            [y.reshape(e_loc * cap_loc, d),
             jnp.zeros((cap_loc + 1, d), dt)], axis=0)
        back = y_flat[jnp.minimum(slot_r, e_loc * cap_loc + cap_loc)]
        back = jnp.where(keep_r[:, None], back, 0.0)

        ret = jax.lax.all_to_all(back, "model", split_axis=0,
                                 concat_axis=0, tiled=True)
        ret_all = jnp.concatenate([ret, jnp.zeros((1, d), dt)], axis=0)
        out_rep = ret_all[jnp.minimum(slot, m_size * cap_send)]
        out_rep = jnp.where(keep[:, None], out_rep, 0.0)
        w = gates_loc.reshape(a_loc, 1).astype(dt)
        return jnp.sum((out_rep * w).reshape(n_loc, k, d), axis=1)

    out_flat = jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(tok_spec, tok_spec, tok_spec,
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=tok_spec,
    )(xf, expert_idx, gate_vals.astype(dt),
      # cast before the boundary: the FSDP weight all-gather implied by the
      # in_spec then moves bf16, not fp32 (halves that wire volume)
      p["wi_gate"].astype(dt), p["wi_up"].astype(dt), p["wo"].astype(dt))

    out = out_flat.reshape(b, s, d)
    if cfg.num_shared_experts:
        out = out + L.mlp_apply(p["shared"], x)
    out = wlc(out, ("batch", None, None))
    return out, aux_loss
