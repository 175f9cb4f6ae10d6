"""Explicit shard_map collectives — the §Perf hillclimb implementations.

GSPMD gets the baselines right for dense matmuls but falls over on two
patterns this framework hits hard (evidence: analysis.hlo.collective_sites
on the compiled baselines, recorded in EXPERIMENTS.md §Perf):

1. DECODE ATTENTION over a sequence-sharded KV cache: the attention einsum
   prefers head sharding, so GSPMD involuntarily all-gathers the entire
   cache every step (gemma3-12b decode_32k: 4.7 GB/chip/token).
   -> ``decode_attention_sharded``: distributed flash-decoding.  Each model
   shard attends over its local cache slice, then one pmax (B,H) + two psum
   (B,H,D)/(B,H) merge the partial softmaxes.  Ring insert is shard-local.

2. MoE DISPATCH: the (E,C,d) scatter forces GSPMD to materialize the full
   expert buffer per shard and all-reduce it (olmoe prefill_32k:
   260 GB of all-reduce in the HLO, 150 GB temp per chip).
   -> ``moe_block_ep``: expert parallelism over the "model" axis.  Tokens
   stay replicated across the model axis (they are sharded over "data"),
   each shard routes/dispatches only to its E/16 local experts, and one
   psum of the (T_loc, d) partial outputs combines — the same wire cost as
   a dense tensor-parallel MLP, with no giant buffer.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class DistConfig:
    """Distribution context threaded through forward() when explicit
    (beyond-GSPMD) collectives are requested."""
    mesh: object                          # jax Mesh
    data_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    moe_impl: str = "gspmd"               # "gspmd" | "ep"
    decode_attn_impl: str = "gspmd"       # "gspmd" | "sharded"
    seq_parallel: bool = False            # Megatron-SP residual layout

    @property
    def model_size(self) -> int:
        return dict(zip(self.mesh.axis_names,
                        self.mesh.devices.shape))[self.model_axis]


# ---------------------------------------------------------------------------
# 1. distributed flash decoding + shard-local ring insert
# ---------------------------------------------------------------------------
def decode_attention_sharded(dist: DistConfig, q, k_cache, v_cache, k_new,
                             v_new, cache_len, *, circular: bool,
                             window: int = 0, logit_cap: float = 0.0):
    """q: (B,1,H,D); caches (B,S,KH,D) seq-sharded over the model axis.

    Inserts (k_new, v_new) at cache_len (ring if circular) LOCALLY on the
    owning shard, then flash-decodes across shards.  Returns
    (out (B,1,H,D), new_k, new_v).
    """
    mesh = dist.mesh
    ax = dist.model_axis
    dp = dist.data_axes
    b = q.shape[0]
    dp_spec = dp if b % _axes_size(mesh, dp) == 0 else None

    qspec = P(dp_spec, None, None, None)       # replicated over model
    cspec = P(dp_spec, ax, None, None)         # seq-sharded cache

    def local_fn(q, k_loc, v_loc, k_new, v_new, cache_len):
        n_shards = jax.lax.psum(1, ax)
        shard = jax.lax.axis_index(ax)
        s_loc = k_loc.shape[1]
        smax = s_loc * n_shards
        pos = cache_len % smax if circular else jnp.minimum(cache_len,
                                                            smax - 1)
        # ---- shard-local insert ----
        local_slot = pos - shard * s_loc
        in_range = (local_slot >= 0) & (local_slot < s_loc)
        slot = jnp.clip(local_slot, 0, s_loc - 1)
        old_k = jax.lax.dynamic_slice_in_dim(k_loc, slot, 1, 1)
        old_v = jax.lax.dynamic_slice_in_dim(v_loc, slot, 1, 1)
        ins_k = jnp.where(in_range, k_new.astype(k_loc.dtype), old_k)
        ins_v = jnp.where(in_range, v_new.astype(v_loc.dtype), old_v)
        k_loc = jax.lax.dynamic_update_slice_in_dim(k_loc, ins_k, slot, 1)
        v_loc = jax.lax.dynamic_update_slice_in_dim(v_loc, ins_v, slot, 1)

        # ---- local flash-decode ----
        # grouped-head einsum: never materialize the GQA-repeated or
        # f32-cast cache (PERF iter 2: cuts ~3 cache-sized copies/layer)
        bq, _, h, d = q.shape
        kh = k_loc.shape[2]
        g = h // kh
        qg = (q[:, 0].astype(jnp.float32) * (d ** -0.5)
              ).reshape(bq, kh, g, d)
        scores = jnp.einsum("bkgd,bskd->bkgs", qg, k_loc,
                            preferred_element_type=jnp.float32)
        if logit_cap:
            scores = jnp.tanh(scores / logit_cap) * logit_cap
        gpos = shard * s_loc + jnp.arange(s_loc)
        n_valid = cache_len + 1
        if circular:
            valid = gpos < jnp.minimum(n_valid, smax)
        else:
            valid = gpos < n_valid
            if window:
                valid &= gpos > n_valid - 1 - window
        scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
        m_loc = scores.max(axis=-1)                              # (B,KH,G)
        m = jax.lax.pmax(m_loc, ax)
        p = jnp.exp(scores - m[..., None])
        l_loc = p.sum(axis=-1)
        acc_loc = jnp.einsum("bkgs,bskd->bkgd", p, v_loc,
                             preferred_element_type=jnp.float32)
        l = jax.lax.psum(l_loc, ax)
        acc = jax.lax.psum(acc_loc, ax)
        out = (acc / jnp.maximum(l, 1e-20)[..., None]).astype(q.dtype)
        return out.reshape(bq, 1, h, d), k_loc, v_loc

    fn = shard_map(
        local_fn, mesh=mesh,
        in_specs=(qspec, cspec, cspec,
                  P(dp_spec, None, None, None), P(dp_spec, None, None, None),
                  P()),
        out_specs=(P(dp_spec, None, None, None), cspec, cspec),
        check_vma=False)
    out, new_k, new_v = fn(q, k_cache, v_cache, k_new, v_new,
                           jnp.asarray(cache_len, jnp.int32))
    # out from local_fn is (B,1,H,D) already
    return out.reshape(q.shape), new_k, new_v


def _axes_size(mesh, axes) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in (axes if isinstance(axes, (tuple, list)) else [axes]):
        n *= sizes[a]
    return n


# ---------------------------------------------------------------------------
# 2. expert-parallel MoE
# ---------------------------------------------------------------------------
def moe_block_ep(dist: DistConfig, params, x, *, num_experts: int,
                 top_k: int, capacity_factor: float = 1.25,
                 capacity: int = 0):
    """Expert-parallel MoE: experts sharded over the model axis, tokens
    sharded over data / replicated over model.  Combine = one psum of the
    (B_loc,S,d) partial outputs (dense-TP wire cost).

    Requires num_experts % model_axis_size == 0 (olmoe 64/16 OK; granite 40
    falls back to the GSPMD path at the call site)."""
    mesh = dist.mesh
    ax = dist.model_axis
    dp = dist.data_axes
    b, s, d = x.shape
    n_model = dist.model_size
    assert num_experts % n_model == 0
    e_loc = num_experts // n_model
    dp_spec = dp if b % _axes_size(mesh, dp) == 0 else None

    def local_fn(router, gate, up, down, x):
        # x: (B_loc, S, d); router (d, E) replicated; expert tables local
        bl, sl, dl = x.shape
        t = bl * sl
        xf = x.reshape(t, dl)
        dtype = x.dtype
        logits = (xf @ router.astype(dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, top_k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        # aux loss: identical across model shards, but token means must
        # average over the data axis (tokens are data-sharded)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(expert_ids, num_experts,
                                     dtype=jnp.float32).sum(1), axis=0) / top_k
        if dp:
            me = jax.lax.pmean(me, dp)
            ce = jax.lax.pmean(ce, dp)
        aux = num_experts * jnp.sum(me * ce)

        cap = capacity if capacity > 0 else int(
            max(top_k, t * top_k / num_experts * capacity_factor))
        e0 = jax.lax.axis_index(ax) * e_loc
        flat_expert = expert_ids.reshape(-1)
        flat_gate = gate_vals.reshape(-1)
        flat_token = jnp.repeat(jnp.arange(t), top_k)
        local_eid = flat_expert - e0
        is_local = (local_eid >= 0) & (local_eid < e_loc)
        sort_key = jnp.where(is_local, local_eid, e_loc)   # non-local last
        order = jnp.argsort(sort_key, stable=True)
        sorted_eid = sort_key[order]
        sorted_token = flat_token[order]
        sorted_gate = flat_gate[order]
        seg_cum = jnp.cumsum(
            jax.nn.one_hot(sorted_eid, e_loc + 1, dtype=jnp.int32), axis=0)
        pos_in_e = jnp.take_along_axis(
            seg_cum, sorted_eid[:, None], axis=1)[:, 0] - 1
        keep = (sorted_eid < e_loc) & (pos_in_e < cap)
        slot = jnp.where(keep, sorted_eid * cap + pos_in_e, e_loc * cap)
        buf = jnp.zeros((e_loc * cap + 1, dl), dtype)
        buf = buf.at[slot].set(jnp.where(keep[:, None], xf[sorted_token], 0))
        buf = buf[:-1].reshape(e_loc, cap, dl)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, gate.astype(dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", buf, up.astype(dtype))
        y = jnp.einsum("ecf,efd->ecd", h, down.astype(dtype))
        y = jnp.concatenate([y.reshape(e_loc * cap, dl),
                             jnp.zeros((1, dl), dtype)], axis=0)
        contrib = y[slot] * (sorted_gate[:, None].astype(dtype)
                             * keep[:, None].astype(dtype))
        out = jnp.zeros((t, dl), dtype).at[sorted_token].add(contrib)
        out = jax.lax.psum(out, ax)            # combine expert partials
        return out.reshape(bl, sl, dl), aux

    fn = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), P(ax, None, None), P(ax, None, None),
                  P(ax, None, None), P(dp_spec, None, None)),
        out_specs=(P(dp_spec, None, None), P()),
        check_vma=False)
    out, aux = fn(params["router"], params["gate"], params["up"],
                  params["down"], x)
    return out, aux


# ---------------------------------------------------------------------------
# 3. tensor-parallel experts (any expert count)
# ---------------------------------------------------------------------------
def moe_block_tp(dist: DistConfig, params, x, *, num_experts: int,
                 top_k: int, capacity_factor: float = 1.25,
                 capacity: int = 0):
    """TP-experts MoE for expert counts that do NOT divide the model axis
    (granite's 40e over 16): every model shard holds ALL experts but only
    ff/n_model columns of each expert's FFN.  Dispatch buffers are built
    from LOCAL tokens only (no GSPMD full-buffer all-reduce)
    and one psum of (T_loc, d) partial outputs combines, exactly like
    ``moe_block_ep``.  Wire cost identical to EP; compute identical to the
    reference (no replication waste)."""
    mesh = dist.mesh
    ax = dist.model_axis
    dp = dist.data_axes
    b, s, d = x.shape
    dp_spec = dp if b % _axes_size(mesh, dp) == 0 else None

    def local_fn(router, gate, up, down, x):
        bl, sl, dl = x.shape
        t = bl * sl
        xf = x.reshape(t, dl)
        dtype = x.dtype
        logits = (xf @ router.astype(dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, top_k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(expert_ids, num_experts,
                                     dtype=jnp.float32).sum(1), axis=0) / top_k
        if dp:
            me = jax.lax.pmean(me, dp)
            ce = jax.lax.pmean(ce, dp)
        aux = num_experts * jnp.sum(me * ce)

        cap = capacity if capacity > 0 else int(
            max(top_k, t * top_k / num_experts * capacity_factor))
        flat_expert = expert_ids.reshape(-1)
        flat_gate = gate_vals.reshape(-1)
        flat_token = jnp.repeat(jnp.arange(t), top_k)
        order = jnp.argsort(flat_expert, stable=True)
        sorted_eid = flat_expert[order]
        sorted_token = flat_token[order]
        sorted_gate = flat_gate[order]
        seg_cum = jnp.cumsum(
            jax.nn.one_hot(sorted_eid, num_experts, dtype=jnp.int32), axis=0)
        pos_in_e = jnp.take_along_axis(
            seg_cum, sorted_eid[:, None], axis=1)[:, 0] - 1
        keep = pos_in_e < cap
        slot = jnp.where(keep, sorted_eid * cap + pos_in_e,
                         num_experts * cap)
        buf = jnp.zeros((num_experts * cap + 1, dl), dtype)
        buf = buf.at[slot].set(jnp.where(keep[:, None], xf[sorted_token], 0))
        buf = buf[:-1].reshape(num_experts, cap, dl)
        # ff-sharded expert FFN: local columns, full contraction on down
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, gate.astype(dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", buf, up.astype(dtype))
        y = jnp.einsum("ecf,efd->ecd", h, down.astype(dtype))  # partial in d
        y = jnp.concatenate([y.reshape(num_experts * cap, dl),
                             jnp.zeros((1, dl), dtype)], axis=0)
        contrib = y[slot] * (sorted_gate[:, None].astype(dtype)
                             * keep[:, None].astype(dtype))
        out = jnp.zeros((t, dl), dtype).at[sorted_token].add(contrib)
        out = jax.lax.psum(out, ax)            # combine ff partials
        return out.reshape(bl, sl, dl), aux

    fn = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), P(None, None, ax), P(None, None, ax),
                  P(None, ax, None), P(dp_spec, None, None)),
        out_specs=(P(dp_spec, None, None), P()),
        check_vma=False)
    out, aux = fn(params["router"], params["gate"], params["up"],
                  params["down"], x)
    return out, aux
