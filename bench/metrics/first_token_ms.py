"""Median over requests of the milliseconds from the start of its batch's
S1 (program span ``s1.stage``) to its first token on the host (the end of
its ``s4.prefill``), in the traced part of the window."""
from bench import program_spans


def read(w):
    return program_spans.first_token_ms(w)
