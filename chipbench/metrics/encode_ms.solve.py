"""Mean host-clock milliseconds of the program's ``encode`` span per
solve: the per-solve encode of [X | y] (``core.data_parallel``
``make_encoded_problem``), which ends after the encoded blocks are read
back to the host and uploaded again.  The span is taken by
``run.program_spans``, with the program's obs recorder off, so the solves
run the same path as in a ``--trace 0`` window."""


def read(ctx):
    durs = [s.dur for s in ctx.spans if s.name == "encode"]
    if not durs:
        return None
    return 1e3 * sum(durs) / len(durs)
