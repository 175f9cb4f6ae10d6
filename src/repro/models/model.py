"""Composable decoder/encoder model over the block zoo.

One code path serves all 12 configs (10 assigned + the paper's embedder and
generator).  The layer stack is ``lax.scan`` over ``depth_repeat`` groups of
``cfg.block_pattern`` blocks — HLO size stays flat in depth, which keeps the
512-way SPMD dry-run compile tractable and matches MaxText's scanned-layers
design.  ``shared_attn`` blocks (zamba2) close over a single unstacked param
set reused at every application.

Public entry points:
  init_params / forward / loss_fn       (training & encoding)
  prefill  / decode_step                (serving; see launch/ and serving/)
  encode                                (the embedding model used by EdgeRAG)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models.cache import KVCache, init_cache, kv_cache_spec
from repro.models.layers import (apply_mrope, apply_rope, dense_init,
                                 init_mlp, init_rms_norm, mlp, rms_norm)
from repro.models.mamba2 import init_mamba2, mamba2_mixer
from repro.models.moe import init_moe, moe_block
from repro.models.rwkv6 import init_rwkv6, rwkv6_block

ATTN_KINDS = ("attn", "swa", "shared_attn", "moe", "swa_moe")
# KV-block chunked-attention threshold: sequences longer than this lower the
# online-softmax scan instead of the quadratic reference.
CHUNKED_ATTN_MIN_SEQ = 2048


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_attn_block(key, cfg: ModelConfig, kind: str):
    ks = jax.random.split(key, 6)
    p = {
        "norm1": init_rms_norm(cfg.d_model),
        "wq": dense_init(ks[0], (cfg.d_model, cfg.q_dim)),
        "wk": dense_init(ks[1], (cfg.d_model, cfg.kv_dim)),
        "wv": dense_init(ks[2], (cfg.d_model, cfg.kv_dim)),
        "wo": dense_init(ks[3], (cfg.q_dim, cfg.d_model)),
        "norm2": init_rms_norm(cfg.d_model),
    }
    if kind in ("moe", "swa_moe"):
        p["moe"] = init_moe(ks[4], cfg.d_model, cfg.d_ff, cfg.num_experts)
    else:
        p["mlp"] = init_mlp(ks[4], cfg.d_model, cfg.d_ff)
    return p


def _init_block(key, cfg: ModelConfig, kind: str):
    if kind in ATTN_KINDS:
        return _init_attn_block(key, cfg, kind)
    if kind == "mamba2":
        return {"norm1": init_rms_norm(cfg.d_model),
                "mixer": init_mamba2(key, cfg)}
    if kind == "rwkv6":
        return init_rwkv6(key, cfg)
    raise ValueError(kind)


def init_params(cfg: ModelConfig, key, dtype=jnp.float32) -> Dict[str, Any]:
    keys = jax.random.split(key, len(cfg.block_pattern) + 3)
    params: Dict[str, Any] = {}
    params["embed"] = dense_init(keys[0], (cfg.vocab_size, cfg.d_model),
                                 scale=0.02)
    blocks = []
    shared = None
    for i, kind in enumerate(cfg.block_pattern):
        if kind == "shared_attn":
            shared = _init_block(keys[i + 1], cfg, kind)
            blocks.append(None)  # placeholder; closed over, not scanned
            continue
        layer_keys = jax.random.split(keys[i + 1], cfg.depth_repeat)
        blocks.append(jax.vmap(lambda k: _init_block(k, cfg, kind))(layer_keys))
    params["blocks"] = tuple(blocks)
    if shared is not None:
        params["shared"] = shared
    params["final_norm"] = init_rms_norm(cfg.d_model)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[-1], (cfg.d_model, cfg.vocab_size))
    if dtype != jnp.float32:
        params = jax.tree.map(lambda a: a.astype(dtype), params)
    return params


def param_count(params) -> int:
    # shared blocks appear once in the tree, so this is exact
    return sum(a.size for a in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------
def _attention_sub_block(p, x, cfg: ModelConfig, kind: str, *, positions,
                         causal, mode, cache: Optional[KVCache], cache_len,
                         window_mode: bool, attn_impl: str, dist=None):
    b, s, _ = x.shape
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    q = (h @ p["wq"].astype(x.dtype)).reshape(b, s, cfg.num_heads,
                                              cfg.head_dim)
    k = (h @ p["wk"].astype(x.dtype)).reshape(b, s, cfg.num_kv_heads,
                                              cfg.head_dim)
    v = (h @ p["wv"].astype(x.dtype)).reshape(b, s, cfg.num_kv_heads,
                                              cfg.head_dim)
    if cfg.use_mrope:
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    window = cfg.sliding_window if kind in ("swa", "swa_moe") else 0
    new_cache = cache
    if mode == "decode":
        assert cache is not None and s == 1
        _, circular = kv_cache_spec(cfg, kind, cache.k.shape[1],
                                    window_mode=window_mode)
        # window_mode rings every attention layer (DESIGN.md §4)
        circular = circular or window_mode
        if dist is not None and dist.decode_attn_impl == "sharded":
            from repro.models.distributed import decode_attention_sharded
            out, nk, nv = decode_attention_sharded(
                dist, q, cache.k, cache.v, k, v, cache_len,
                circular=circular, window=window,
                logit_cap=cfg.attn_logit_softcap)
            new_cache = KVCache(nk, nv)
        else:
            new_cache = cache.insert(k, v, cache_len, circular=circular)
            out = attn_lib.attend_decode(
                q, new_cache.k, new_cache.v, jnp.asarray(cache_len) + 1,
                window=window, logit_cap=cfg.attn_logit_softcap,
                circular=circular)
    else:
        if mode == "prefill" and cache is not None:
            _, circular = kv_cache_spec(cfg, kind, cache.k.shape[1],
                                        window_mode=window_mode)
            if circular:
                # ring invariant: token p lives at slot p % size.  Scatter
                # the last `size` tokens to their ring slots (static idx).
                size = cache.k.shape[1]
                if s <= size:
                    new_cache = cache.insert(k, v, 0, circular=False)
                else:
                    pos = jnp.arange(s - size, s) % size
                    new_cache = KVCache(
                        cache.k.at[:, pos].set(k[:, -size:].astype(cache.k.dtype)),
                        cache.v.at[:, pos].set(v[:, -size:].astype(cache.v.dtype)))
            else:
                new_cache = cache.insert(k, v, cache_len, circular=False)
        use_chunked = (attn_impl == "chunked"
                       or (attn_impl == "auto" and s >= CHUNKED_ATTN_MIN_SEQ))
        if use_chunked:
            out = attn_lib.attend_chunked(
                q, k, v, causal=causal, window=window,
                logit_cap=cfg.attn_logit_softcap)
        else:
            out = attn_lib.attend_reference(
                q, k, v, causal=causal, window=window,
                logit_cap=cfg.attn_logit_softcap)
    out = out.reshape(b, s, cfg.q_dim)
    return x + out @ p["wo"].astype(x.dtype), new_cache


def apply_block(kind: str, p, x, cfg: ModelConfig, *, positions, causal,
                mode, cache, cache_len, window_mode, attn_impl, dist=None):
    """Returns (x_out, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ATTN_KINDS:
        with jax.named_scope("attention"):
            x, new_cache = _attention_sub_block(
                p, x, cfg, kind, positions=positions, causal=causal,
                mode=mode, cache=cache, cache_len=cache_len,
                window_mode=window_mode, attn_impl=attn_impl, dist=dist)
        with jax.named_scope("mlp"):
            h = rms_norm(x, p["norm2"], cfg.norm_eps)
            if kind in ("moe", "swa_moe"):
                # decode is dropless: capacity = T covers the all-to-one
                # worst case
                cap = x.shape[0] * x.shape[1] if mode == "decode" else 0
                if dist is not None and dist.moe_impl == "ep":
                    if cfg.num_experts % dist.model_size == 0:
                        from repro.models.distributed import (
                            moe_block_ep as _moe)
                    else:
                        # non-divisible expert count: TP-experts
                        # (ff-sharded)
                        from repro.models.distributed import (
                            moe_block_tp as _moe)
                    y, aux = _moe(
                        dist, p["moe"], h, num_experts=cfg.num_experts,
                        top_k=cfg.num_experts_per_tok,
                        capacity_factor=cfg.expert_capacity_factor,
                        capacity=cap)
                else:
                    y, aux = moe_block(
                        p["moe"], h, num_experts=cfg.num_experts,
                        top_k=cfg.num_experts_per_tok,
                        capacity_factor=cfg.expert_capacity_factor,
                        capacity=cap)
            else:
                y = mlp(p["mlp"], h)
        return x + y, new_cache, aux
    if kind == "mamba2":
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        y, new_cache = mamba2_mixer(p["mixer"], h, cfg, cache)
        return x + y, new_cache, aux
    if kind == "rwkv6":
        x, new_cache = rwkv6_block(p, x, cfg, cache)
        return x, new_cache, aux
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# stack
# ---------------------------------------------------------------------------
def _run_stack(params, x, cfg: ModelConfig, *, positions, causal, mode,
               caches, cache_len, window_mode, attn_impl, remat,
               unroll_layers: bool = False, dist=None):
    shared = params.get("shared")
    pattern = cfg.block_pattern

    sp_sharding = None
    if (dist is not None and dist.seq_parallel
            and mode in ("train", "prefill")):
        # Megatron-style sequence parallelism: the residual stream lives
        # sequence-sharded over the model axis between blocks, turning the
        # TP all-reduces into reduce-scatter + all-gather pairs (half the
        # ring payload) and sharding block-boundary elementwise work
        from jax.sharding import NamedSharding, PartitionSpec as P
        sp_sharding = NamedSharding(
            dist.mesh, P(dist.data_axes, dist.model_axis, None))

    def group(x, group_params, group_caches):
        new_caches = []
        aux_total = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(pattern):
            p = shared if kind == "shared_attn" else group_params[i]
            c = group_caches[i] if group_caches is not None else None
            x, nc, aux = apply_block(
                kind, p, x, cfg, positions=positions, causal=causal,
                mode=mode, cache=c, cache_len=cache_len,
                window_mode=window_mode, attn_impl=attn_impl, dist=dist)
            if sp_sharding is not None:
                x = jax.lax.with_sharding_constraint(x, sp_sharding)
            new_caches.append(nc)
            aux_total = aux_total + aux
        return x, tuple(new_caches), aux_total

    if remat:
        group = jax.checkpoint(group)

    # xs: stacked params per pattern position (None for shared slots)
    stacked = tuple(p for p in params["blocks"])

    if unroll_layers:
        # dry-run accounting mode: XLA's cost_analysis counts a while body
        # ONCE, so the roofline run unrolls the layer loop to get true
        # per-step FLOPs/bytes/collectives.  Real runs keep the scan.
        aux_total = jnp.zeros((), jnp.float32)
        ys = []
        for r in range(cfg.depth_repeat):
            gp, gc = jax.tree.map(lambda a: a[r], (stacked, caches))
            x, ncs, aux = group(x, gp, gc)
            ys.append(ncs)
            aux_total = aux_total + aux
        new_caches = jax.tree.map(lambda *a: jnp.stack(a), *ys)
        return x, new_caches, aux_total

    # the caches ride in the scan's carry, so a program that is donated
    # them updates their buffer in place; as scanned inputs and outputs
    # they would be written to a second buffer
    def body(carry, gp):
        x, cs, i = carry
        gc = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False), cs)
        x, ncs, aux = group(x, gp, gc)
        if cs is not None:
            cs = jax.tree.map(
                lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, i, 0),
                cs, ncs)
        return (x, cs, i + 1), aux

    (x, new_caches, _), auxs = jax.lax.scan(
        body, (x, caches, jnp.zeros((), jnp.int32)), stacked,
        length=cfg.depth_repeat)
    return x, new_caches, jnp.sum(auxs)


def _embed_inputs(params, cfg: ModelConfig, batch, compute_dtype):
    if batch.get("embeds") is not None:
        x = batch["embeds"].astype(compute_dtype)
    else:
        x = params["embed"][batch["tokens"]].astype(compute_dtype)
    if "vision_embeds" in batch and batch["vision_embeds"] is not None:
        ve = batch["vision_embeds"].astype(compute_dtype)  # (B, P, d)
        x = jax.lax.dynamic_update_slice(x, ve, (0, 0, 0))  # image prefix
    return x


def _default_positions(cfg: ModelConfig, b, s, offset=0):
    off = jnp.asarray(offset, jnp.int32)
    if off.ndim == 1:
        off = off[:, None]                     # per-slot offsets (B, 1)
    pos = jnp.arange(s, dtype=jnp.int32)[None, :] + off
    pos = jnp.broadcast_to(pos, (b, s))
    if cfg.use_mrope:
        pos = jnp.broadcast_to(pos[None], (3, b, s))  # text: t=h=w
    return pos


def _logits(params, cfg: ModelConfig, x):
    with jax.named_scope("output_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if cfg.tie_embeddings:
            return x @ params["embed"].astype(x.dtype).T
        return x @ params["lm_head"].astype(x.dtype)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
def forward(params, cfg: ModelConfig, batch, *, mode: str = "train",
            caches=None, cache_len=0, causal: bool = True,
            window_mode: bool = False, attn_impl: str = "auto",
            compute_dtype=jnp.float32, remat: Optional[bool] = None,
            unroll_layers: bool = False, dist=None):
    """Returns (logits, new_caches, aux_loss)."""
    x = _embed_inputs(params, cfg, batch, compute_dtype)
    b, s = x.shape[:2]
    positions = batch.get("positions")
    if positions is None:
        offset = cache_len if mode == "decode" else 0
        positions = _default_positions(cfg, b, s, offset)
    if remat is None:
        remat = mode == "train"
    x, new_caches, aux = _run_stack(
        params, x, cfg, positions=positions, causal=causal, mode=mode,
        caches=caches, cache_len=cache_len, window_mode=window_mode,
        attn_impl=attn_impl, remat=remat, unroll_layers=unroll_layers,
        dist=dist)
    logits = _logits(params, cfg, x)
    return logits, new_caches, aux


def loss_fn(params, cfg: ModelConfig, batch, *, compute_dtype=jnp.float32,
            attn_impl: str = "auto", dist=None):
    """Next-token cross-entropy + MoE load-balance aux."""
    logits, _, aux = forward(params, cfg, batch, mode="train",
                             compute_dtype=compute_dtype,
                             attn_impl=attn_impl, dist=dist)
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones_like(ll)
    ce = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    total = ce + cfg.router_aux_loss_coef * aux
    return total, {"ce": ce, "aux": aux}


def prefill(params, cfg: ModelConfig, batch, caches, *,
            window_mode: bool = False, compute_dtype=jnp.float32,
            attn_impl: str = "auto"):
    """Run the full prompt; fills caches.  Returns (last_logits, caches)."""
    logits, new_caches, _ = forward(
        params, cfg, batch, mode="prefill", caches=caches, cache_len=0,
        window_mode=window_mode, compute_dtype=compute_dtype,
        attn_impl=attn_impl, remat=False)
    return logits[:, -1], new_caches


def decode_step(params, cfg: ModelConfig, tokens_or_embeds, caches,
                cache_len, *, window_mode: bool = False,
                compute_dtype=jnp.float32):
    """One-token serve step.  tokens: (B, 1) int32 (or (B,1,d) embeds).

    Returns (logits (B, vocab), new_caches).
    """
    if tokens_or_embeds.ndim == 2:
        batch = {"tokens": tokens_or_embeds}          # audio decodes codec ids
    else:
        batch = {"embeds": tokens_or_embeds.astype(compute_dtype)}
    logits, new_caches, _ = forward(
        params, cfg, batch, mode="decode", caches=caches,
        cache_len=cache_len, window_mode=window_mode,
        compute_dtype=compute_dtype, remat=False)
    return logits[:, 0], new_caches


def encode(params, cfg: ModelConfig, batch, *, compute_dtype=jnp.float32,
           attn_impl: str = "auto"):
    """Bidirectional mean-pooled sentence embedding (the gte model)."""
    x = _embed_inputs(params, cfg, batch, compute_dtype)
    b, s = x.shape[:2]
    positions = batch.get("positions")
    if positions is None:
        positions = _default_positions(cfg, b, s)
    x, _, _ = _run_stack(params, x, cfg, positions=positions, causal=False,
                         mode="train", caches=None, cache_len=0,
                         window_mode=False, attn_impl=attn_impl, remat=False)
    with jax.named_scope("pooling"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        mask = batch.get("attn_mask")
        if mask is None:
            emb = x.mean(axis=1)
        else:
            m = mask.astype(x.dtype)[..., None]
            emb = (x * m).sum(1) / jnp.maximum(m.sum(1), 1.0)
        return emb / jnp.linalg.norm(emb, axis=-1,
                                     keepdims=True).clip(1e-9)
