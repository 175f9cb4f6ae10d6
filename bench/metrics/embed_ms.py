"""Host milliseconds per request in the query embedding call that the
serving loop makes (``ModelEmbedder``, span ``embed.query``), over the
batches that started after the traced part of the window."""


def read(w):
    return w.per_request_ms("embed.query")
