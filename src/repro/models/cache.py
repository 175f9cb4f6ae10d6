"""Decode-time state: KV caches (full / circular sliding-window) and the
SSM states defined by the mixer modules.

All caches are stacked over ``depth_repeat`` (leading axis R) per pattern
position so the layer scan can thread them.  ``cache_len`` is a scalar —
the framework decodes synchronized batches (continuous batching tracks
per-slot lengths one level up, in serving/engine.py).

Whether a KV cache is a ring buffer is STATIC information derived from
(block kind, window_mode) via :func:`kv_cache_spec` — it is intentionally
not stored on the pytree so caches stay pure arrays for pjit.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.configs.shapes import LONG_CONTEXT_WINDOW


class KVCache(NamedTuple):
    k: jax.Array          # (B, Smax, KH, D), or (R, B, Smax, KH, D) stacked
    v: jax.Array

    def insert(self, k_new, v_new, cache_len, *, circular: bool,
               layer=None):
        """Insert (..., B, S_new, KH, D) at cache_len (mod size if ring
        buffer): rows of one layer's cache, or of every layer of a stack
        (R, B, Smax, KH, D); with ``layer``, rows (B, S_new, KH, D) of
        that layer of a stack.

        cache_len may be a scalar (synchronized batch) or a (B,) vector of
        per-slot lengths (continuous batching, S_new must be 1, no
        ``layer``)."""
        pos = cache_len % self.k.shape[-3] if circular else cache_len

        def put(buf, new):
            new = new.astype(buf.dtype)
            if jnp.ndim(pos) == 1:             # per-slot scatter, S_new == 1
                rows = jnp.arange(buf.shape[-4])
                return buf.at[..., rows, pos, :, :].set(new[..., 0, :, :])
            start = (0,) * (buf.ndim - 3) + (pos, 0, 0)
            if layer is not None:
                new, start = new[None], (layer,) + start[1:]
            return jax.lax.dynamic_update_slice(buf, new, start)
        return KVCache(put(self.k, k_new), put(self.v, v_new))

    def layer(self, i) -> "KVCache":
        """Layer ``i`` of a stacked cache."""
        return KVCache(*(jax.lax.dynamic_index_in_dim(a, i, 0, False)
                         for a in self))

    def with_row(self, k_new, v_new, cache_len, *, circular: bool):
        """The cache as :meth:`insert` leaves it for one new token
        (B, 1, KH, D), selected row by row instead of written: a reader
        fuses the select and reads the cache where it lies."""
        smax = self.k.shape[-3]
        pos = cache_len % smax if circular else cache_len
        if jnp.ndim(pos) == 0:                 # where insert's slice clamps
            pos = jnp.minimum(pos, smax - 1)
        at = (jnp.arange(smax) == jnp.reshape(pos, (-1, 1)))[..., None, None]
        return KVCache(*(jnp.where(at, new.astype(a.dtype), a)
                         for a, new in ((self.k, k_new), (self.v, v_new))))


def kv_cache_spec(cfg: ModelConfig, kind: str, max_len: int,
                  *, window_mode: bool) -> Tuple[int, bool]:
    """(cache_size, circular) for an attention block kind."""
    if kind in ("swa", "swa_moe") and cfg.sliding_window:
        return min(cfg.sliding_window, max_len), True
    if window_mode:
        # long-context serving mode: every attention layer gets a ring
        # buffer of the serving window (DESIGN.md §4)
        return min(LONG_CONTEXT_WINDOW, max_len), True
    return max_len, False


def init_layer_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     *, window_mode: bool, dtype=jnp.float32):
    from repro.models.mamba2 import init_mamba_cache
    from repro.models.rwkv6 import init_rwkv_cache
    if kind in ("attn", "swa", "shared_attn", "moe", "swa_moe"):
        size, _ = kv_cache_spec(cfg, kind, max_len, window_mode=window_mode)
        shape = (batch, size, cfg.num_kv_heads, cfg.head_dim)
        return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
    if kind == "mamba2":
        return init_mamba_cache(cfg, batch, dtype)
    if kind == "rwkv6":
        return init_rwkv_cache(cfg, batch, dtype)
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, *,
               window_mode: bool = False, dtype=jnp.float32):
    """Tuple over pattern positions; each leaf stacked over depth_repeat."""
    caches = []
    for kind in cfg.block_pattern:
        single = init_layer_cache(cfg, kind, batch, max_len,
                                  window_mode=window_mode, dtype=dtype)
        stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.depth_repeat,) + a.shape),
            single)
        caches.append(stacked)
    return tuple(caches)


def cache_bytes(cache) -> int:
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves(cache) if hasattr(a, "size"))
