"""Host milliseconds per request in ``RAGEngine.stage_decode`` (S4: prefill
and greedy decode; span ``s4.generate``), over the batches that started
after the traced part of the window."""


def read(w):
    return w.per_request_ms("s4.generate")
