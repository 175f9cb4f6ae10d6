"""A decode step writes one row into each layer of the stacked KV cache.

After a prefill and one decode step, both on donated caches through the
layer scan, every position of every layer's K and V is bit for bit what
the prefill left, except the row the step wrote; that row is bit for bit
the K/V of the layers unrolled one by one (``unroll_layers``), and differs
from what was there.  Cases: full caches, sliding-window ring buffers that
the prompt has wrapped, ``window_mode`` rings, and per-slot lengths as
``ContinuousBatcher`` passes them."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import forward, init_cache, init_params
from repro.models.cache import kv_cache_spec

PROMPT = 24


def run(cfg, params, toks, lens, max_len, *, scanned, window_mode):
    """The caches after a prefill of ``toks`` and after one decode step at
    ``lens``, as numpy arrays per pattern position: (K, V) each."""
    def prefill(p, b, c):
        return forward(p, cfg, b, mode="prefill", caches=c, cache_len=0,
                       window_mode=window_mode, remat=False,
                       unroll_layers=not scanned)[:2]

    def decode(p, b, c, n):
        return forward(p, cfg, b, mode="decode", caches=c, cache_len=n,
                       window_mode=window_mode, remat=False,
                       unroll_layers=not scanned)[:2]
    donate = {"donate_argnums": 2} if scanned else {}
    caches = init_cache(cfg, toks.shape[0], max_len, window_mode=window_mode)
    logits, caches = jax.jit(prefill, **donate)(params, {"tokens": toks},
                                                caches)
    before = [tuple(np.asarray(a) for a in c) for c in caches]
    tok = logits[:, -1].argmax(-1).astype(jnp.int32)[:, None]
    _, caches = jax.jit(decode, **donate)(params, {"tokens": tok}, caches,
                                          lens)
    return before, [tuple(np.asarray(a) for a in c) for c in caches]


@pytest.mark.parametrize("arch,window,max_len,window_mode,slots", [
    pytest.param("stablelm-1.6b", 0, 32, False, None, id="attn"),
    # 12 layers (two scan steps): five of six ring buffers of 16 slots,
    # which the prompt has wrapped, beside one full cache
    pytest.param("gemma3-12b", 16, 32, False, None, id="swa-wrapped"),
    # every layer a ring of 16 slots, which the prompt has wrapped
    pytest.param("stablelm-1.6b", 0, 16, True, None, id="window_mode"),
    pytest.param("stablelm-1.6b", 0, 32, False, (24, 13, 5), id="per-slot"),
])
def test_decode_writes_one_row_per_layer(arch, window, max_len, window_mode,
                                         slots):
    cfg = configs.get_config(arch).reduced(
        num_layers=12 if arch == "gemma3-12b" else 2)
    if window:
        cfg = dataclasses.replace(cfg, sliding_window=window)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = 1 if slots is None else len(slots)
    toks = jax.random.randint(jax.random.PRNGKey(1), (batch, PROMPT), 1,
                              cfg.vocab_size)
    lens = PROMPT if slots is None else jnp.asarray(slots, jnp.int32)
    before, after = run(cfg, params, toks, lens, max_len, scanned=True,
                        window_mode=window_mode)
    _, unrolled = run(cfg, params, toks, lens, max_len, scanned=False,
                      window_mode=window_mode)
    lens = np.broadcast_to(np.asarray(lens), (batch,))
    for kind, was, now, ref in zip(cfg.block_pattern, before, after,
                                   unrolled, strict=True):
        for a, b, r in zip(was, now, ref, strict=True):
            size = a.shape[-3]
            _, ring = kv_cache_spec(cfg, kind, size, window_mode=window_mode)
            rows = lens % size if ring else lens
            written = np.zeros(a.shape[:-2], bool)      # (R, B, Smax)
            written[:, np.arange(batch), rows] = True
            np.testing.assert_array_equal(b[~written], a[~written])
            np.testing.assert_array_equal(b[written], r[written])
            assert not np.array_equal(b[written], a[written])
