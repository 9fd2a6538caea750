"""Model step: decode-program device time over the scan steps it ran."""


def read(view):
    tr, c = view.trace, view.costs
    if tr is None or c is None or c.decode_steps <= 0 \
            or tr.program_s(view.decode_program) <= 0:
        return None
    return 1e3 * tr.program_s(view.decode_program) / c.decode_steps
