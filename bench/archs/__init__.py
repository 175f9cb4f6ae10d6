"""Model architectures the benchmark can build, check and count.

A model entry of a configuration file names its architecture under
``"arch"`` (``"dense"`` when absent); ``bench.models.arch`` imports the
module of that name from this package.  A new architecture enters as one
new module here, beside a configuration file that names it.  Each module
provides, for a model entry ``m``:

``program_config(m)``
    the program's ``ModelConfig``;
``init_weights(m, seed, topic_of_id=None)``
    seeded weights on the default device, in one jitted call, in the
    layout the program takes as ``params=``;
``param_count(m)``
    the parameters of the model as the file states it;
``logits(params, m, tokens, positions, control=None)`` (a generator)
    the plain reference's float32 logits of one causal token row;
``encode(params, m, tokens, mask, control=None, block=64)`` (an encoder)
    the plain reference's unit-norm embeddings of padded token rows;
``generator_flops(m, prompt_len, new_tokens)``, ``encoder_flops(m, lengths)``
    forward FLOPs of the work the algorithm asks for: for a sparse layer,
    only the experts a token is routed to;
``small(m, layers, width, vocab)``
    the model cut to a size the CPU tests can run.

The references import nothing of the program but ``ModelConfig`` (inside
``program_config``); the arithmetic they share is in ``bench.models``.
"""
