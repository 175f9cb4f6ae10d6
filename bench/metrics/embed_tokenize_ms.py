"""Host milliseconds per request in the query embedding's tokenizer and row
padding (program span ``embed.tokenize``, outside S2's regeneration), in
the traced part of the window."""
from bench import program_spans


def read(w):
    return program_spans.per_request_ms(w, "embed.tokenize", outside="s2.")
