"""Kernel costs for roofline shares, one ``<kernel>.py`` each, found by
the kernel's name. A file here defines:

    OP                 a regular expression that matches the names of the
                       kernel's ops in the trace (``<module>/<op>``)
    calls(ctx, n)      the shapes of the ``n`` calls of those ops that the
                       traced steps hold, as a list of dicts, worked out
                       from ``ctx`` (``bench.trace.Context``: the cell's
                       configuration and traffic, the steps' live context
                       lengths, the number of chips)
    flops(shape)       the operations that one call needs
    bytes(shape)       the bytes that one call moves to or from HBM

A per-layer metric ``<kernel>_roofline`` is then the one-line reader
``def read(ctx): return ctx.roofline("<kernel>")``: the sum over the
calls of the larger of ``flops / peak FLOP/s`` and ``bytes / peak
bytes/s``, over the device time of the kernel's ops, in %; None where no
op of the kernel ran. No kernel runs on the default training path yet, so
there is none here.
"""
