"""The dense block: weights, the plain reference, FLOPs and the CPU cut.

The block as a configuration file states it, in straightforward
``jax.numpy``: pre-norm RMSNorm with a ``1 + w`` gain, rotary embedding
over the whole head, softmax attention (bidirectional for the encoder,
causal for the generator), a SwiGLU MLP, a final norm; the encoder
mean-pools over its real tokens and normalises, the generator applies an
untied output head.  It runs one layer at a time, in the arithmetic of
``bench.models`` (float32 at ``highest``, or a ``control``), with weights
in the dtype the file states.  The weights are made in the layout the
program's dense path takes (``params=``).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.models import DTYPES, _einsum, _mm, _rms, _rope


def program_config(m: dict):
    """The program's ``ModelConfig`` for a configuration file's model."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=m["name"], arch_type="dense", num_layers=m["num_layers"],
        d_model=m["hidden_size"], num_heads=m["num_heads"],
        num_kv_heads=m["num_kv_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        block_pattern=("attn",), rope_theta=m["rope_theta"],
        tie_embeddings=m["tie_embeddings"], norm_eps=m["norm_eps"])


def param_count(m: dict) -> int:
    """Parameters of the dense block stack as the file states it."""
    d, q, kv, ff = (m["hidden_size"], m["num_heads"] * m["head_dim"],
                    m["num_kv_heads"] * m["head_dim"], m["intermediate_size"])
    per_layer = d * (q + 2 * kv) + q * d + 2 * d + 3 * d * ff
    head = 0 if m["tie_embeddings"] else m["vocab_size"] * d
    return m["vocab_size"] * d + head + d + m["num_layers"] * per_layer


def init_weights(m: dict, seed: int, topic_of_id=None):
    """Seeded weights on the default device, in one jitted program.

    Projections are normal with std ``1/sqrt(fan_in)``, the embedding
    std 0.02, norm gains normal with std 0.1 (so a path that ignored them
    would show).  The padding id's embedding row is zero.  With
    ``topic_of_id`` (``bench.models.topic_rows``), each embedding row of a
    topic's word also carries that topic's own random direction, of the
    same size as its noise: words of one topic embed near each other, as a
    trained encoder's do, so that queries find their topic's passages."""
    dt = DTYPES[m["dtype"]]
    L, d, v = m["num_layers"], m["hidden_size"], m["vocab_size"]
    q = m["num_heads"] * m["head_dim"]
    kv = m["num_kv_heads"] * m["head_dim"]
    ff = m["intermediate_size"]
    shapes = {"embed": ((v, d), 0.02), "final_norm": ((d,), 0.1)}
    layer = {"norm1": ((L, d), 0.1), "wq": ((L, d, q), d ** -0.5),
             "wk": ((L, d, kv), d ** -0.5), "wv": ((L, d, kv), d ** -0.5),
             "wo": ((L, q, d), q ** -0.5), "norm2": ((L, d), 0.1)}
    mlp = {"gate": ((L, d, ff), d ** -0.5), "up": ((L, d, ff), d ** -0.5),
           "down": ((L, ff, d), ff ** -0.5)}
    if not m["tie_embeddings"]:
        shapes["lm_head"] = ((d, v), d ** -0.5)
    if topic_of_id is None:
        topic_of_id = np.full(v, -1, np.int32)
    n_topics = int(topic_of_id.max()) + 1

    def make(key, topic_of_id):
        leaves = {**shapes, **layer, **{"mlp." + k: s for k, s in mlp.items()}}
        names = sorted(leaves) + ["topics"]
        keys = dict(zip(names, jax.random.split(key, len(names))))
        w = {name: jax.random.normal(keys[name], shape, jnp.float32) * std
             for name, (shape, std) in leaves.items()}
        dirs = jax.random.normal(keys["topics"], (max(1, n_topics), d),
                                 jnp.float32) * 0.02
        has = (topic_of_id >= 0)[:, None]
        topic = dirs[jnp.maximum(topic_of_id, 0)]
        emb = jnp.where(has, (w["embed"] + topic) / 2 ** 0.5, w["embed"])
        w["embed"] = emb.at[0].set(0.0)
        w = {k: a.astype(dt) for k, a in w.items()}
        block = {k: w[k] for k in layer}
        block["mlp"] = {k: w["mlp." + k] for k in mlp}
        out = {"embed": w["embed"], "blocks": (block,),
               "final_norm": w["final_norm"]}
        if "lm_head" in w:
            out["lm_head"] = w["lm_head"]
        return out
    return jax.jit(make)(jax.random.PRNGKey(seed), jnp.asarray(topic_of_id))


def small(m: dict, layers: int, width: int, vocab: int) -> dict:
    """The model ``layers`` deep and ``width`` wide in heads of 64, with an
    MLP twice as wide and a ``vocab``-id vocabulary: a CPU test's size."""
    return dict(m, num_layers=layers, hidden_size=width,
                num_heads=width // 64, num_kv_heads=width // 64, head_dim=64,
                intermediate_size=2 * width, vocab_size=vocab)


# ---------------------------------------------------------------------------
# the reference, one layer at a time
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "causal",
                                             "theta", "eps", "control"))
def _layer(x, p, *, heads, kv_heads, causal, theta, eps, control):
    b, s, d = x.shape
    h = _rms(x, p["norm1"], eps)
    hd = p["wq"].shape[-1] // heads
    q = _rope(_mm(h, p["wq"], control).reshape(b, s, heads, hd), theta)
    k = _rope(_mm(h, p["wk"], control).reshape(b, s, kv_heads, hd), theta)
    v = _mm(h, p["wv"], control).reshape(b, s, kv_heads, hd)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    att = _einsum("bqhd,bkhd->bhqk", q, k, control) * hd ** -0.5
    if causal:
        att = jnp.where(jnp.tril(jnp.ones((s, s), bool)), att, -1e30)
    att = jax.nn.softmax(att, axis=-1)
    o = _einsum("bhqk,bkhd->bqhd", att, v, control)
    x = x + _mm(o.reshape(b, s, heads * hd), p["wo"], control)
    h = _rms(x, p["norm2"], eps)
    g = jax.nn.silu(_mm(h, p["mlp"]["gate"], control))
    return x + _mm(g * _mm(h, p["mlp"]["up"], control), p["mlp"]["down"],
                   control)


def _stack(params, m: dict, tokens, causal: bool, control):
    x = params["embed"][tokens].astype(jnp.float32)
    block = params["blocks"][0]
    for i in range(m["num_layers"]):
        p = jax.tree.map(lambda a: a[i], block)
        x = _layer(x, p, heads=m["num_heads"], kv_heads=m["num_kv_heads"],
                   causal=causal, theta=float(m["rope_theta"]),
                   eps=float(m["norm_eps"]), control=control)
    return _rms(x, params["final_norm"], m["norm_eps"])


def encode(params, m: dict, tokens: np.ndarray, mask: np.ndarray,
           control=None, block: int = 64) -> np.ndarray:
    """Unit-norm mean-pooled embeddings of (B, S) padded token rows, in
    blocks of ``block`` rows.  Attention runs over every position, padding
    included, and pooling over the masked-in ones, as the program's
    encoder does."""
    out = []
    for s in range(0, len(tokens), block):
        t, mk = tokens[s:s + block], mask[s:s + block]
        n = len(t)
        if n < block:                       # one compiled shape
            t = np.pad(t, ((0, block - n), (0, 0)))
            mk = np.pad(mk, ((0, block - n), (0, 0)), constant_values=1)
        x = _stack(params, m, jnp.asarray(t), False, control)
        mf = jnp.asarray(mk, jnp.float32)[..., None]
        e = (x * mf).sum(1) / jnp.maximum(mf.sum(1), 1.0)
        e = e / jnp.maximum(jnp.linalg.norm(e, axis=-1, keepdims=True),
                            1e-9)
        out.append(np.asarray(e, np.float64)[:n])
    return np.concatenate(out) if out else np.zeros((0, m["hidden_size"]))


@functools.partial(jax.jit, static_argnames=("control",))
def _head(params, x, control):
    w = params.get("lm_head")
    w = params["embed"].T if w is None else w
    return _mm(x, w, control)


def logits(params, m: dict, tokens: np.ndarray, positions: np.ndarray,
           control=None) -> np.ndarray:
    """Causal forward over one (S,) token row; float32 logits at
    ``positions``."""
    x = _stack(params, m, jnp.asarray(tokens)[None], True, control)
    return np.asarray(_head(params, x[0, jnp.asarray(positions)], control))


# ---------------------------------------------------------------------------
# model FLOPs: the work the algorithm asks for, from shapes
# ---------------------------------------------------------------------------
def non_embedding_params(m: dict) -> int:
    """Parameters a token's forward pass multiplies by, per token: the
    block stack (norms included) and the final norm; the output head is
    counted apart, since only decoded positions use it."""
    d, q = m["hidden_size"], m["num_heads"] * m["head_dim"]
    kv, ff = m["num_kv_heads"] * m["head_dim"], m["intermediate_size"]
    return m["num_layers"] * (d * (q + 2 * kv) + q * d + 2 * d + 3 * d * ff) + d


def encoder_flops(m: dict, lengths: Sequence[int]) -> float:
    """Forward FLOPs of the bidirectional encoder over rows of ``lengths``
    real tokens: 2 x parameters per token plus attention (scores and
    weighted sum, 2 x 2 x ctx x q_dim per layer per token)."""
    n, qd, L = non_embedding_params(m), m["num_heads"] * m["head_dim"], \
        m["num_layers"]
    return float(sum(2 * n * t + 4 * L * qd * t * t for t in lengths))


def generator_flops(m: dict, prompt_len: int, new_tokens: int) -> float:
    """Forward FLOPs of one causal generation: the prompt's real tokens,
    then the ``new_tokens - 1`` generated tokens fed back; token i attends
    to i + 1 positions; the output head runs once per generated token."""
    n, qd, L = non_embedding_params(m), m["num_heads"] * m["head_dim"], \
        m["num_layers"]
    total = prompt_len + new_tokens - 1
    attn = 4 * L * qd * total * (total + 1) / 2
    head = 2 * m["hidden_size"] * m["vocab_size"] * new_tokens
    return float(2 * n * total + attn + head)
