"""Model FLOPs of the window's requests over the seconds the serving loop
was busy with batches, times the chips and their bf16 peak, in percent;
over the batches that started after the traced part of the window.

Real tokens only: each query and each regenerated passage through the
encoder, each prompt and the generated tokens through the generator
(``bench/flops.py``).  Busy seconds, not the window: at a fixed offered
rate the window's work is fixed, so a share of the window could not move.
"""
from bench import flops


def read(w):
    if w.peak is None:
        return None
    enc, gen = w.config["encoder"], w.config["generator"]
    lengths = [min(enc["max_len"], n) for n in w.query_tokens]
    lengths += w.regen_tokens
    total = flops.encoder_flops(enc, lengths)
    total += sum(flops.generator_flops(gen, min(gen["max_prompt"], n),
                                       gen["max_new_tokens"])
                 for n in w.prompt_tokens)
    busy = w.spans.total("bench.batch")
    if not busy:
        return None
    return 100.0 * total / (busy * w.chips * w.peak["bf16_flops_per_s"])
