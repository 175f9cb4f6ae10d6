"""Host milliseconds per request that S4 spends before its prefill: the
prompt's tokenizer and left pad, and a new KV cache (program spans
``s4.tokenize`` and ``s4.kv_init``), in the traced part of the window."""
from bench import program_spans


def read(w):
    return program_spans.per_request_ms(w, "s4.tokenize", "s4.kv_init")
