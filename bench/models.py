"""The arithmetic every model's plain reference shares, and the lookup of
a model's architecture.

The benchmark makes the weights itself, in one jitted call per model from
the seed, in the layout the program takes (``params=``), so a reference
never reads anything the program made.  Each architecture's weights,
reference, parameter and FLOP counts are a module of ``bench/archs``
(``arch``), found by the ``"arch"`` of the model's entry in its
configuration file.

References run one layer at a time, in float32 at ``highest`` matmul
precision (``_mm``, ``_einsum``), with weights in the dtype the file
states.  ``control=`` computes the same in other arithmetic:
``"bfloat16"`` operands (what the program's float32 matmuls are on a TPU)
or ``"int8"`` weights on top of that, the precision control that
``correct`` must tell apart (``control_of``).
"""
from __future__ import annotations

import importlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
ARCHS = Path(__file__).resolve().parent / "archs"


def arch(m: dict):
    """The module of ``bench/archs`` that the model entry's ``"arch"``
    names (``"dense"`` when absent); an unknown name raises."""
    name = m.get("arch", "dense")
    if str(name).isidentifier():
        try:
            return importlib.import_module(f"bench.archs.{name}")
        except ModuleNotFoundError as e:
            if e.name != f"bench.archs.{name}":
                raise
    have = sorted(f.stem for f in ARCHS.glob("*.py") if f.stem != "__init__")
    raise ValueError(f"model {m.get('name')!r}: no architecture {name!r} in "
                     f"bench/archs/ (have {have})")


def topic_rows(vocab: int, topics) -> np.ndarray:
    """(vocab,) topic of each token id that spells a topic word, else -1
    (ids as the program's word tokenizer gives them)."""
    from bench.check import fnv1a
    rows = np.full(vocab, -1, np.int32)
    for t, words in enumerate(topics):
        for w in words:
            rows[2 + fnv1a(w) % (vocab - 2)] = t
    return rows


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def _rope(x, theta):
    """x (B, S, H, D): rotate the two halves of each head by position."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _quant_int8(w):
    """Symmetric int8 per output channel, dequantised (the control)."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    return jnp.round(w / jnp.maximum(scale, 1e-30)).clip(-127, 127) * scale


def _mm(x, w, control):
    """x @ w in the reference's arithmetic, float32 at highest precision;
    or with bfloat16 operands and float32 accumulation (``"bfloat16"``,
    what a float32 matmul is at JAX's default precision on a TPU); or the
    same with weights rounded to int8 first (``"int8"``)."""
    if control is None:
        return jnp.matmul(x, w.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
    if control == "int8":
        w = _quant_int8(w)
    return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _einsum(spec, a, b, control):
    """An activation-by-activation product: float32 at highest precision,
    or with bfloat16 operands under any ``control``."""
    if control is None:
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def control_of(m: dict) -> str:
    """The next precision below the one the model's file states.  Both
    files state matmuls at JAX's default TPU precision (bfloat16 operands,
    float32 accumulation), so the control rounds the weights to int8."""
    return {"bfloat16": "int8"}[m["matmul_operands"]]
