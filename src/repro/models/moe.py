"""Mixture-of-Experts layer (OLMoE / DeepSeek-V2 style).

Top-k routing with shared experts and capacity-bounded dispatch.  Two
execution paths share the routing math:

* ``_apply_moe_local`` — single-shard gather/scatter dispatch (CPU tests,
  single-device training, and the per-shard body below).
* ``apply_moe_sharded`` — explicit ``shard_map`` distribution: tokens stay
  sharded over the dp axes, experts over ``model``.  Every (data, model)
  shard routes its *local* tokens against the full router (x is replicated
  across ``model``, so routing agrees across model-ranks), gathers the
  subset destined to its *local* experts, runs the expert MLPs, scatter-adds
  a partial output and ``psum``s over ``model`` — the same all-reduce TP
  already pays for the dense FFN, so MoE costs no extra collective class.
  This dispatch is all-to-all-free and sort-free by construction.

Why explicit shard_map: XLA's SPMD propagation cannot shard the
gather/scatter dispatch from shardings alone — it replicates the expert
matmuls on every device (measured 143x the expected per-device FLOPs on
olmoe train_4k; EXPERIMENTS.md §Dry-run).

Capacity: C = ceil(T_local * k / E * capacity_factor); overflow tokens fall
back to the shared experts / residual path (GShard semantics, applied
per-shard as in GShard/MaxText).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import apply_mlp, dense_init, init_mlp

Params = Dict[str, Any]


def init_moe(key, d_model: int, moe_d_ff: int, n_experts: int,
             n_shared: int, act: str, dtype=jnp.float32) -> Params:
    """Experts are stored stacked: w1/w3 (E, d, ff), w2 (E, ff, d)."""
    ks = jax.random.split(key, 5)
    scale_in = 1.0 / math.sqrt(d_model)
    scale_out = 1.0 / math.sqrt(moe_d_ff)

    def stack(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    p: Params = {
        "router": dense_init(ks[0], d_model, n_experts, jnp.float32),
        "w1": stack(ks[1], (n_experts, d_model, moe_d_ff), scale_in),
        "w3": stack(ks[2], (n_experts, d_model, moe_d_ff), scale_in),
        "w2": stack(ks[3], (n_experts, moe_d_ff, d_model), scale_out),
    }
    if n_shared > 0:
        p["shared"] = init_mlp(ks[4], d_model, moe_d_ff * n_shared, act, dtype)
    return p


def _ambient_mesh():
    from jax._src.mesh import thread_resources
    m = thread_resources.env.physical_mesh
    return None if m is None or m.empty else m


# --------------------------------------------------------------- routing
def _route(xf, router, n_experts: int, top_k: int, router_aux_weight: float):
    """Token routing + Switch aux loss.  xf: (T, d) -> gates (T,k) idx (T,k).

    The router matmul keeps activations in their compute dtype and
    accumulates in f32 (``preferred_element_type``) — upcasting the whole
    (T, d) stream to f32 first materializes it through HBM once per MoE
    layer per pass (measured ~23 GB/step/device on olmoe train_4k, §Perf
    C2) for zero accuracy benefit over f32 accumulation.
    """
    logits = jnp.einsum("td,de->te", xf, router.astype(xf.dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # (T, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)        # (T, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    onehot_any = jax.nn.one_hot(gate_idx, n_experts, dtype=jnp.float32)
    frac = jnp.mean(jnp.sum(onehot_any, axis=1), axis=0)     # (E,)
    aux = router_aux_weight * n_experts * jnp.sum(
        frac * jnp.mean(probs, axis=0))
    return gate_vals, gate_idx, aux


def _dispatch_compute(p, xf, gate_vals, gate_idx, *, e_local: int,
                      expert_offset, capacity: int, act: str, dtype):
    """Gather local-expert tokens, run expert MLPs, scatter-add partials.

    xf: (T, d); gate_idx holds GLOBAL expert ids; this shard owns experts
    [expert_offset, expert_offset + e_local).  Returns (T, d) partial out.
    """
    t, d = xf.shape
    top_k = gate_idx.shape[-1]
    flat_e = gate_idx.reshape(-1) - expert_offset            # local coords
    local = (flat_e >= 0) & (flat_e < e_local)
    flat_e = jnp.where(local, flat_e, 0)

    # position of each (token, slot) assignment within its local expert
    onehot = jax.nn.one_hot(flat_e, e_local, dtype=jnp.int32
                            ) * local[:, None].astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) * onehot                # (T*k, E_loc)
    pos_in_e = jnp.sum(pos, axis=-1) - 1                     # (T*k,)
    tok_ids = jnp.repeat(jnp.arange(t), top_k)
    keep = local & (pos_in_e >= 0) & (pos_in_e < capacity)

    idx_table = jnp.full((e_local, capacity), t, jnp.int32)
    idx_table = idx_table.at[flat_e, pos_in_e].set(
        jnp.where(keep, tok_ids, t), mode="drop")
    gate_table = jnp.zeros((e_local, capacity), jnp.float32)
    gate_table = gate_table.at[flat_e, pos_in_e].set(
        jnp.where(keep, gate_vals.reshape(-1), 0.0), mode="drop")

    xpad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    g = xpad[idx_table].astype(dtype)                        # (E_loc, C, d)
    h = jnp.einsum("ecd,edf->ecf", g, p["w1"].astype(dtype))
    h = jax.nn.silu(h) if act in ("swiglu",) else jax.nn.gelu(h)
    if act in ("swiglu", "geglu"):
        h = h * jnp.einsum("ecd,edf->ecf", g, p["w3"].astype(dtype))
    y = jnp.einsum("ecf,efd->ecd", h, p["w2"].astype(dtype))
    y = y * gate_table[..., None].astype(dtype)

    out = jnp.zeros((t + 1, d), dtype)
    out = out.at[idx_table.reshape(-1)].add(y.reshape(-1, d))
    return out[:t]


def _apply_moe_local(p: Params, x: jax.Array, *, n_experts: int, top_k: int,
                     act: str, dtype, capacity_factor: float = 1.25,
                     router_aux_weight: float = 0.01):
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    gate_vals, gate_idx, aux = _route(xf, p["router"], n_experts, top_k,
                                      router_aux_weight)
    capacity = max(int(math.ceil(t * top_k / n_experts * capacity_factor)),
                   top_k)
    out = _dispatch_compute(p, xf, gate_vals, gate_idx, e_local=n_experts,
                            expert_offset=0, capacity=capacity, act=act,
                            dtype=dtype)
    out = out.reshape(b, s, d)
    if "shared" in p:
        out = out + apply_mlp(p["shared"], x, act, dtype)
    return out, aux


def apply_moe_sharded(p: Params, x: jax.Array, *, mesh, n_experts: int,
                      top_k: int, act: str, dtype,
                      capacity_factor: float = 1.25,
                      router_aux_weight: float = 0.01):
    """shard_map dispatch: tokens over dp axes, experts over ``model``.

    Two layouts:

    * train/prefill (seq > 1): tokens stay dp-sharded; expert weights enter
      at their model shard (ZeRO-3 storage is re-gathered over dp — the
      standard weight gather, amortized over the big token batch).
    * decode (seq == 1): tokens are tiny, weights are the traffic — expert
      weights enter 2D-sharded (experts x model, FF x data) matching
      ZeRO-3 storage exactly (zero resharding), every rank computes an
      (expert-slice, ff-slice) partial and ONE psum over (model, data)
      completes it.  Measured on deepseek-v2 decode_32k: removes the
      per-step expert-weight all-gather (§Perf B3).
    """
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_model = mesh.shape["model"]
    e_local = n_experts // n_model
    b, s, d = x.shape
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    ff = p["w1"].shape[-1]
    decode_2d = (s == 1 and dp and ff % dp_size == 0 and ff >= dp_size)

    batch_entry = (dp if len(dp) > 1 else dp[0]) if (
        not decode_2d and dp and b % dp_size == 0 and b >= dp_size) else None
    t_local = (b // dp_size if batch_entry else b) * s
    capacity = max(int(math.ceil(
        t_local * top_k / n_experts * capacity_factor)), top_k)

    dp_entry = dp if len(dp) > 1 else dp[0]
    x_spec = P(batch_entry, None, None)
    if decode_2d:
        w_specs = {"router": P(None, None),
                   "w1": P("model", None, dp_entry),
                   "w3": P("model", None, dp_entry),
                   "w2": P("model", dp_entry, None)}
        if "shared" in p:
            w_specs["shared"] = {"w1": P(None, "model"),
                                 "w3": P(None, "model"),
                                 "w2": P("model", None)}
    else:
        w_specs = {"router": P(None, None),
                   "w1": P("model", None, None), "w3": P("model", None, None),
                   "w2": P("model", None, None)}
        if "shared" in p:
            w_specs["shared"] = {"w1": P(None, "model"),
                                 "w3": P(None, "model"),
                                 "w2": P("model", None)}
    w_specs = {k: w_specs[k] for k in p}  # preserve pytree structure

    def body(p_loc, x_loc):
        bl, sl, _ = x_loc.shape
        xf = x_loc.reshape(bl * sl, d)
        gate_vals, gate_idx, aux = _route(xf, p_loc["router"], n_experts,
                                          top_k, router_aux_weight)
        offset = jax.lax.axis_index("model") * e_local
        out = _dispatch_compute(p_loc, xf, gate_vals, gate_idx,
                                e_local=e_local, expert_offset=offset,
                                capacity=capacity, act=act, dtype=dtype)
        out = out.reshape(bl, sl, d)
        if "shared" in p_loc:
            # local ff-slice of the shared-expert MLP; the ff contraction
            # in w2 makes it a TP partial the psum below completes
            shared = apply_mlp(p_loc["shared"], x_loc, act, dtype)
            if decode_2d:
                # every data-rank computes the same shared partial; scale
                # so the (model, data) psum sums it exactly once
                shared = shared / dp_size
            out = out + shared
        axes = ("model",) + dp if decode_2d else ("model",)
        out = jax.lax.psum(out, axes)
        if dp:
            aux = jax.lax.pmean(aux, dp)
        return out, aux

    fn = jax.shard_map(body, mesh=mesh, in_specs=(w_specs, x_spec),
                       out_specs=(x_spec, P()))
    return fn(p, x)


def apply_moe(p: Params, x: jax.Array, *, n_experts: int, top_k: int,
              act: str, dtype, capacity_factor: float = 1.25,
              router_aux_weight: float = 0.01):
    """x: (B, S, d) -> (out (B,S,d), aux_loss scalar).

    Returns the load-balancing auxiliary loss (Switch-style) so training
    can add it; serving callers drop it.  Under a multi-device mesh context
    the dispatch runs through :func:`apply_moe_sharded` (see module
    docstring for why SPMD propagation alone is not enough).
    """
    mesh = _ambient_mesh()
    if (mesh is not None and mesh.size > 1 and "model" in mesh.axis_names
            and n_experts % mesh.shape["model"] == 0):
        return apply_moe_sharded(
            p, x, mesh=mesh, n_experts=n_experts, top_k=top_k, act=act,
            dtype=dtype, capacity_factor=capacity_factor,
            router_aux_weight=router_aux_weight)
    return _apply_moe_local(p, x, n_experts=n_experts, top_k=top_k, act=act,
                            dtype=dtype, capacity_factor=capacity_factor,
                            router_aux_weight=router_aux_weight)
