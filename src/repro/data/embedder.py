"""Embedding models behind one protocol: ``embed(texts) -> (n, dim) f32``
(unit-normalized), plus ``dim``.

* :class:`HashingEmbedder` — deterministic char-3-gram random projection.
  Fast and similarity-preserving enough for index unit tests.  Trigram
  hashing runs as a vectorized numpy bulk path (FNV-1a over byte windows),
  so one call over many texts is one feature matmul, not a Python loop per
  character.
* :class:`ModelEmbedder` — the real thing: wraps the gte-base JAX model
  (``repro.models.encode``) behind the tokenizer.  A call runs as
  microbatches of at most ``MAX_BATCH`` rows, each padded to a power-of-two
  row count, so the jitted encode compiles once per bucket and a corpus
  build never compiles a corpus-sized program.
* :class:`TableEmbedder` — oracle for synthetic corpora: chunk texts carry a
  ``doc-<id>`` prefix that resolves to a precomputed vector, so regeneration
  at retrieval time reproduces indexing-time embeddings exactly (the paper's
  determinism assumption for online generation).  Non-oracle rows fall back
  to one batched :class:`HashingEmbedder` call.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import numpy as np

from repro.core.tracing import span
from repro.data.tokenizer import HashingTokenizer, _fnv1a

_FNV_BASIS = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(0x100000001B3)


class HashingEmbedder:
    def __init__(self, dim: int = 768, seed: int = 0, n_features: int = 4096):
        self.dim = dim
        rng = np.random.default_rng(seed)
        self._proj = rng.standard_normal((n_features, dim)).astype(np.float32)
        self._proj /= np.sqrt(n_features)
        self.n_features = n_features
        self.calls = 0
        self.chars_embedded = 0

    def _trigram_hashes(self, text: str) -> np.ndarray:
        """FNV-1a hash of every char trigram, vectorized over byte windows.

        Equivalent to hashing ``text[i:i+3]`` per position when the text is
        pure ASCII (one byte per char); multibyte texts take the exact
        per-character path.
        """
        t = text.lower()
        data = t.encode("utf-8")
        if len(data) != len(t):          # non-ASCII: exact per-char fallback
            return np.asarray(
                [_fnv1a(t[i:i + 3]) for i in range(len(t) - 2)], np.uint64)
        arr = np.frombuffer(data, np.uint8).astype(np.uint64)
        n = len(arr) - 2
        if n <= 0:
            return np.zeros(0, np.uint64)
        with np.errstate(over="ignore"):
            h = np.full(n, _FNV_BASIS, np.uint64)
            for j in range(3):
                h ^= arr[j:j + n]
                h *= _FNV_PRIME          # wraps mod 2^64 like _fnv1a
        return h

    def _features(self, text: str) -> np.ndarray:
        h = self._trigram_hashes(text)
        if len(h) == 0:
            return np.zeros(self.n_features, np.float32)
        return np.bincount(
            (h % np.uint64(self.n_features)).astype(np.int64),
            minlength=self.n_features).astype(np.float32)

    def embed(self, texts: Sequence[str]) -> np.ndarray:
        self.calls += 1
        self.chars_embedded += sum(len(t) for t in texts)
        feats = np.stack([self._features(t) for t in texts])
        out = feats @ self._proj
        norms = np.linalg.norm(out, axis=1, keepdims=True)
        return out / np.clip(norms, 1e-9, None)

    __call__ = embed


class TableEmbedder:
    """Oracle lookup for synthetic corpora (texts carry 'doc-<id> ...')."""

    def __init__(self, table: Dict[int, np.ndarray], dim: int):
        self.table = table
        self.dim = dim
        self.calls = 0
        self.chars_embedded = 0
        self._fallback = HashingEmbedder(dim=dim, seed=1)

    def embed(self, texts: Sequence[str]) -> np.ndarray:
        self.calls += 1
        self.chars_embedded += sum(len(t) for t in texts)
        out = np.empty((len(texts), self.dim), np.float32)
        misses: List[int] = []
        for i, t in enumerate(texts):
            if t.startswith("doc-"):
                did = int(t[4:t.index(" ")] if " " in t else t[4:])
                out[i] = self.table[did]
            else:
                misses.append(i)
        if misses:                       # one batched fallback call
            out[misses] = self._fallback.embed([texts[i] for i in misses])
        return out

    __call__ = embed


class ModelEmbedder:
    """gte-base-en-v1.5 (paper Table 3) running in this framework."""

    # rows per encode program: at 128 tokens the full-width model needs
    # about 1 GB of temporaries, so a corpus build fits beside a generator
    MAX_BATCH = 256

    def __init__(self, cfg=None, params=None, *, max_len: int = 128,
                 seed: int = 0, reduced: bool = True):
        import jax
        from repro.configs import get_config
        from repro.models import encode, init_params
        self._encode = encode
        if cfg is None:
            cfg = get_config("gte-base-en-v1.5")
            if reduced:
                cfg = cfg.reduced(num_layers=2, d_model=256)
        self.cfg = cfg
        self.dim = cfg.d_model
        if params is None:
            params = init_params(cfg, jax.random.PRNGKey(seed))
        self.params = params
        self.tokenizer = HashingTokenizer(vocab_size=cfg.vocab_size)
        self.max_len = max_len
        self.calls = 0
        self.chars_embedded = 0

    @functools.cached_property
    def _jit_encode(self):
        import jax
        encode, cfg = self._encode, self.cfg

        def encoder_forward(params, tokens, attn_mask):
            return encode(params, cfg,
                          {"tokens": tokens, "attn_mask": attn_mask})
        return jax.jit(encoder_forward)

    def embed(self, texts: Sequence[str]) -> np.ndarray:
        """Batched encode in microbatches of at most ``MAX_BATCH`` rows.
        Each microbatch is padded to the next power-of-two row count, so
        the jitted program compiles once per bucket and no call, however
        large (a corpus build), compiles a program bigger than
        ``MAX_BATCH`` rows.  All microbatches are dispatched before the
        first result is read back.  Spans: ``embed.tokenize`` (tokenizer
        and row padding), ``embed.encode`` (dispatch through readback)."""
        self.calls += 1
        self.chars_embedded += sum(len(t) for t in texts)
        with span("embed.tokenize", rows=len(texts)):
            toks, mask = self.tokenizer.encode_batch(list(texts),
                                                     self.max_len)
            parts = [_pad_rows(toks[s:s + self.MAX_BATCH],
                               mask[s:s + self.MAX_BATCH])
                     for s in range(0, toks.shape[0], self.MAX_BATCH)]
        if not parts:
            return np.zeros((0, self.dim), np.float32)
        with span("embed.encode", bucket=len(parts[0][1])):
            outs = [(n, self._jit_encode(self.params, t, m))
                    for n, t, m in parts]
            return np.concatenate([np.asarray(o)[:n] for n, o in outs])

    __call__ = embed


def _pad_rows(t: np.ndarray, m: np.ndarray):
    """(rows, tokens, mask) with the rows padded to the next power of two;
    padded rows keep one valid mask position and are sliced off."""
    n = t.shape[0]
    bucket = 1 << max(0, (n - 1).bit_length())
    if bucket > n:
        pad = ((0, bucket - n), (0, 0))
        t, m = np.pad(t, pad), np.pad(m, pad)
        m[n:, 0] = 1
    return n, t, m
