"""The timed path broken underneath a whole run: ``correct`` comes out
false for each fault a served cell can have, and a sound run passes the
same limits."""
import dataclasses

import pytest

from conftest import CELLS, run_small


def decode_returns_state_unchanged(cell):
    gen = cell.engine.generator
    step = gen._decode
    gen._decode = lambda p, t, c, n: (step(p, t, c, n)[0], c)


def half_the_batch_left_out(cell):
    answer = cell.engine.answer_batch

    def half(queries, embs, get_chunks, **kw):
        out = answer(queries, embs, get_chunks, **kw)
        keep = len(queries) // 2
        return out[:keep] + [dataclasses.replace(r, chunk_ids=[],
                                                 output_tokens=[])
                             for r in out[keep:]]
    cell.engine.answer_batch = half


def token_altered(cell):
    gen = cell.engine.generator
    generate, vocab = gen.generate, gen.cfg.vocab_size

    def altered(prompt, n):
        out = generate(prompt, n)
        return [(out[0] + 1) % vocab] + out[1:]
    gen.generate = altered


def answer_altered(cell):
    finish, n = cell.index.search_finish, len(cell.corpus.texts)

    def altered(state):
        ids, vals, lats = finish(state)
        ids = ids.copy()
        ids[:, 0] = (ids[:, 0] + n // 2) % n
        return ids, vals, lats
    cell.index.search_finish = altered


@pytest.mark.parametrize("fault", [decode_returns_state_unchanged,
                                   half_the_batch_left_out, token_altered,
                                   answer_altered])
def test_fault_is_not_correct(fault):
    r = run_small(CELLS[-1], seed=31, hook=fault)
    assert r["correct"] is False
    over = [k for k, c in r["checks"].items()
            if c["value"] is None or c["value"] > c["limit"]]
    assert over, r["checks"]


def test_sound_run_is_correct():
    assert run_small(CELLS[-1], seed=31)["correct"] is True


def test_slab_topk_choosing_other_rows_is_not_correct(monkeypatch):
    """The S3 kernel broken where the answer is produced: it ranks the
    probed rows by the negated query, so it chooses the worst ones."""
    import repro.core.edgerag as edgerag
    topk = edgerag.slab_topk
    monkeypatch.setattr(edgerag, "slab_topk",
                        lambda emb, q, virt, k, **kw: topk(emb, -q, virt, k,
                                                           **kw))
    r = run_small(CELLS[-1], seed=31)
    assert r["correct"] is False
    assert r["checks"]["slab_gap"]["value"] > r["checks"]["slab_gap"]["limit"]
