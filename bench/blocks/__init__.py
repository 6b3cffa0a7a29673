"""Model blocks, one ``<block>.py`` each, found by the ``"block"`` key of a
configuration file (``bench.common.block``). A file here defines what the
benchmark knows of one family's layers; the shared files (``weights.py``,
``reference.py``, ``flops.py``, ``harness.py``) know none of it:

    PROGRAM_PATH          canonical leaf name -> path in the program's
                          parameter tree
    LAYER_KEYS            the leaves stacked on a leading layer axis
    shapes(m)             canonical leaf shapes for the sizes ``m`` (the
                          configuration's "model")
    init(name, shape, m)  (mean, std) of the leaf's seeded normal values
    program_config(m)     the ``ModelConfig`` overrides, nested sections
                          built as their dataclasses
    layers(m, precision, x, w)
                          the float32 reference of the whole layer stack:
                          (B, T, d) embeddings -> (B, T, d), before the
                          final norm; ``precision`` "fp8" is the control
    matmul_weights(m)     the weights one token multiplies in a forward
                          pass, active ones only, the head included
    attention_flops(m, n) attention over one sequence of ``n`` tokens

The reference side imports nothing of the program: only
``program_config`` may import it, inside the function.
"""
