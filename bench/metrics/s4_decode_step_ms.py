"""Mean host milliseconds of one generator decode step: dispatch, argmax and
the token's readback (program span ``s4.decode_step``), in the traced part
of the window.  A generation's last step reads no token back, so it counts
its dispatch alone."""
from bench import program_spans


def read(w):
    return program_spans.mean_ms(w, "s4.decode_step")
