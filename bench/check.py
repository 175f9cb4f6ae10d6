"""How ``correct`` is decided: the window's answers against the reference.

Run once the window has closed, the device memory peak has been read and
the program's engine is freed.  The reference (``bench/models.py``) reads
only what the benchmark made: the texts, the weights and the served
answers.  Numbers compared, each against the limit of its configuration:

``failed``           requests due in the window that got no answer, fewer
                     than k passages, passages other than those the slab
                     top-k chose, or a wrong number of tokens / a token
                     outside the vocabulary; limit 0.
``query_embed_gap``  widest distance between a served query embedding and
                     the reference's (both unit norm): the query encoder.
``probe_gap``        widest float64 margin by which a centroid the timed
                     probe left out beats one it kept, on the probe launch's
                     own inputs: the S1 top-k.
``slab_gap``         widest float64 margin by which a member row the slab
                     top-k left out beats one it chose, on the launch's own
                     inputs (the packed slab of resolved embeddings and the
                     batch's queries): the S3 top-k.
``score_gap``        (printed, not compared) widest gap between a served
                     passage's score and the reference's float64 product of
                     the reference query and passage embeddings: the
                     resolved embeddings (cache, storage or regeneration by
                     the encoder) and slab scoring.  The precision control
                     reads it only about twice what the program does.
``passage_score_gap`` (printed, not compared) the same gap with the
                     served query embedding in place of the reference's:
                     the passage side alone.
``logit_error``      widest gap between the logit the program gave a served
                     token and the reference's logit of that token, on a
                     seeded sample of requests that includes the longest
                     prompt, the reference teacher-forced on the served
                     tokens: generation.  (The widest gap by which a served
                     token's reference logit lies below the reference's
                     best, ``logit_gap``, is printed beside it: with random
                     weights most positions' margins exceed what the
                     precision control moves a logit, so it reads 0 for
                     the program and the control alike.)
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from bench import models

GEN_SAMPLE = 16          # requests whose tokens the reference re-scores


def fnv1a(word: str) -> int:
    h = 0xCBF29CE484222325
    for ch in word.encode("utf-8"):
        h = ((h ^ ch) * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    return h


class Tokens:
    """The token ids a model sees for a text: a begin id 1, then one id per
    whitespace word of the lowercased text (``2 + fnv1a(word) % (V - 2)``),
    truncated to ``max_len``.  Word ids are remembered, since a corpus
    repeats its vocabulary."""

    def __init__(self, vocab: int):
        self.vocab = vocab
        self.memo: Dict[str, int] = {}

    def ids(self, text: str, max_len: int) -> List[int]:
        memo, v = self.memo, self.vocab - 2
        out = [1]
        for w in text.lower().split()[:max_len - 1]:
            t = memo.get(w)
            if t is None:
                t = memo[w] = 2 + fnv1a(w) % v
            out.append(t)
        return out

    def rows(self, texts: Sequence[str], max_len: int):
        toks = np.zeros((len(texts), max_len), np.int32)
        mask = np.zeros((len(texts), max_len), np.int32)
        for i, t in enumerate(texts):
            ids = self.ids(t, max_len)
            toks[i, :len(ids)] = ids
            mask[i, :len(ids)] = 1
        return toks, mask


def answered(served, k: int, n_tokens: int, vocab: int) -> List[bool]:
    """Whether each request due in the window got a whole answer."""
    ok = []
    for s in served:
        r = None if s is None else s.response
        ok.append(r is not None and s.score is not None
                  and len(r.chunk_ids) == k
                  and len(r.output_tokens) == n_tokens
                  and all(0 <= t < vocab for t in r.output_tokens))
    return ok


def probe_gap(centroids: np.ndarray, probes) -> float:
    """Widest float64 margin by which a left-out centroid outscores a
    probed one, over every probe launch of the window."""
    c64 = np.asarray(centroids, np.float64)
    worst = 0.0
    for q_embs, probed in probes:
        s = np.asarray(q_embs, np.float64) @ c64.T
        for qi, kept in enumerate(probed):
            mask = np.zeros(len(c64), bool)
            mask[kept] = True
            if mask.all() or not mask.any():
                continue
            worst = max(worst, float(s[qi, ~mask].max() - s[qi, mask].min()))
    return worst


def probe_gap_control(centroids: np.ndarray, probes) -> float:
    """``probe_gap`` of the probe computed at ``high`` precision (three
    bfloat16 passes), one step below the kernel's ``highest``."""
    import jax
    import jax.numpy as jnp
    c = jnp.asarray(centroids, jnp.float32)
    ctl = []
    for q_embs, probed in probes:
        s = np.asarray(jnp.matmul(jnp.asarray(q_embs, jnp.float32), c.T,
                                  precision=jax.lax.Precision.HIGH))
        nprobe = max(len(p) for p in probed)
        ctl.append((q_embs, [list(np.argsort(-row, kind="stable")[:nprobe])
                             for row in s]))
    return probe_gap(centroids, ctl)


def _rows64(seg, off: int, n: int) -> np.ndarray:
    """Rows ``off:off + n`` of a slab segment as the kernel scores them."""
    if seg.kind == "pq":
        raise NotImplementedError("slab_gap: PQ segments")
    rows = np.asarray(seg.emb[off:off + n], np.float64)
    if seg.kind == "int8":
        rows = rows * np.asarray(seg.scales[off:off + n], np.float64)
    return rows


def _members(layout, probed):
    """A query's member rows in a packed slab: (rows, their chunk ids)."""
    segs = {seg.kind: seg for seg in layout.segments}
    rows, ids = [], []
    for c in probed:
        kind, off, n = layout.extent[c]
        if n:
            rows.append(_rows64(segs[kind], off, n))
            ids.append(np.asarray(segs[kind].ids[off:off + n]))
    if not rows:
        return np.zeros((0, 1)), np.zeros((0,), np.int64)
    return np.concatenate(rows), np.concatenate(ids)


def slab_gap(probes, slabs, chosen) -> float:
    """Widest float64 margin by which a member row the slab top-k left out
    outscores one it chose, over every batch of the window.  ``probes``:
    per batch (queries, probe lists); ``slabs``: per batch the packed
    ``SlabLayout``; ``chosen``: per batch the (Q, k) chosen chunk ids."""
    worst = 0.0
    for (q_embs, probed), layout, ids in zip(probes, slabs, chosen):
        q = np.asarray(q_embs, np.float64)
        for qi, clusters in enumerate(probed):
            rows, row_ids = _members(layout, clusters)
            picked = np.isin(row_ids, ids[qi][ids[qi] >= 0])
            if picked.all() or not picked.any():
                continue
            s = rows @ q[qi]
            worst = max(worst, float(s[~picked].max() - s[picked].min()))
    return worst


def slab_gap_control(probes, slabs, k: int) -> float:
    """``slab_gap`` of the slab top-k computed at ``high`` precision (three
    bfloat16 passes), one step below the kernel's ``highest``."""
    import jax
    import jax.numpy as jnp
    chosen = []
    for (q_embs, probed), layout in zip(probes, slabs):
        ids = np.full((len(probed), k), -1, np.int64)
        for qi, clusters in enumerate(probed):
            rows, row_ids = _members(layout, clusters)
            if not len(row_ids):
                continue
            s = np.asarray(jnp.matmul(
                jnp.asarray(rows, jnp.float32),
                jnp.asarray(q_embs[qi], jnp.float32),
                precision=jax.lax.Precision.HIGH))
            top = row_ids[np.argsort(-s, kind="stable")[:k]]
            ids[qi, :len(top)] = top
        chosen.append(ids)
    return slab_gap(probes, slabs, chosen)


def retrieval_readings(cell, served, control=None) -> Dict[str, float]:
    """query_embed_gap and score_gap over every served request; with
    ``control`` the reference's precision-control embeddings stand in for
    the program's (its readings, for setting limits)."""
    m = cell.config["encoder"]
    tok = Tokens(m["vocab_size"])
    params = cell.enc_params
    q_texts = [s.query for s in served]
    q_ref = models.encode(params, m, *tok.rows(q_texts, m["max_len"]))
    pids = sorted({int(c) for s in served for c in s.response.chunk_ids})
    col = {p: i for i, p in enumerate(pids)}
    p_ref = models.encode(params, m, *tok.rows(
        cell.corpus.get_chunks(pids), m["max_len"]))
    if control is None:
        q_prog = np.stack([s.embedding for s in served]).astype(np.float64)
        scores = [np.asarray(s.score, np.float64) for s in served]
    else:
        q_prog = models.encode(params, m, *tok.rows(q_texts, m["max_len"]),
                               control=control)
        p_ctl = models.encode(params, m, *tok.rows(
            cell.corpus.get_chunks(pids), m["max_len"]), control=control)
        scores = [p_ctl[[col[int(c)] for c in s.response.chunk_ids]]
                  @ q_prog[i] for i, s in enumerate(served)]
    embed_gap = float(np.linalg.norm(q_prog - q_ref, axis=1).max())
    score_gap = passage_gap = 0.0
    for i, s in enumerate(served):
        rows = p_ref[[col[int(c)] for c in s.response.chunk_ids]]
        score_gap = max(score_gap,
                        float(np.abs(scores[i] - rows @ q_ref[i]).max()))
        passage_gap = max(passage_gap,
                          float(np.abs(scores[i] - rows @ q_prog[i]).max()))
    return {"query_embed_gap": embed_gap, "score_gap": score_gap,
            "passage_score_gap": passage_gap}


def gen_sample(cell, served, seed: int) -> List[int]:
    """A seeded sample of ``GEN_SAMPLE`` requests with the longest prompt
    in it."""
    def words(i):
        texts = cell.corpus.get_chunks(served[i].response.chunk_ids)
        return sum(len(t.split()) for t in texts + [served[i].query])
    longest = max(range(len(served)), key=words)
    rest = [i for i in range(len(served)) if i != longest]
    rng = np.random.default_rng([seed, 3])
    pick = rng.choice(len(rest), size=min(GEN_SAMPLE - 1, len(rest)),
                      replace=False)
    return sorted([longest] + [rest[i] for i in pick])


def logit_readings(cell, served, sample: Sequence[int],
                   control=None) -> Dict[str, float]:
    """At each decoded position of the sampled requests, with the
    reference teacher-forced on the served tokens:

    ``logit_error``  widest gap between the logit the program gave its
                     served token and the reference's logit of that token;
    ``logit_gap``    widest gap by which the served token's reference logit
                     lies below the reference's best.

    With ``control`` the control's first choice and its logit stand in for
    the served token and the program's logit."""
    m = cell.config["generator"]
    tok = Tokens(m["vocab_size"])
    max_prompt, n_new = m["max_prompt"], m["max_new_tokens"]
    error = gap = 0.0
    at = np.arange(n_new)
    for i in sample:
        r = served[i].response
        prompt = " ".join(cell.corpus.get_chunks(r.chunk_ids)
                          + [served[i].query])
        ids = tok.ids(prompt, max_prompt)
        out = list(r.output_tokens)
        row = np.array([0] * (max_prompt - len(ids)) + ids + out[:-1],
                       np.int32)
        pos = np.arange(max_prompt - 1, max_prompt - 1 + n_new)
        ref = models.logits(cell.gen_params, m, row, pos)
        if control is None:
            chosen = np.asarray(out)
            given = np.asarray(served[i].top_logits[:n_new], np.float64)
        else:
            ctl = models.logits(cell.gen_params, m, row, pos,
                                control=control)
            chosen, given = ctl.argmax(-1), ctl.max(-1)
        error = max(error, float(np.abs(given - ref[at, chosen]).max()))
        gap = max(gap, float((ref.max(-1) - ref[at, chosen]).max()))
    return {"logit_error": error, "logit_gap": gap}


def compare(readings: Dict[str, float], limits: Dict[str, float]):
    """(correct, [(name, value, limit)]) over every limit: each reading at
    or under its limit; a limit with no reading fails.  Readings with no
    limit are printed beside them, not compared."""
    rows = [(name, readings.get(name), float(lim))
            for name, lim in limits.items()]
    ok = all(v is not None and float(v) <= lim for _, v, lim in rows)
    return ok, [(n, None if v is None else float(v), lim)
                for n, v, lim in rows]
