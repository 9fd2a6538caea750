"""Model step: model FLOPs of the decoded tokens (costs.token_flops per
delivered token) over decode-program device time x the bf16 peak."""


def read(view):
    tr, c = view.trace, view.costs
    if tr is None or c is None or tr.program_s(view.decode_program) <= 0 \
            or c.decode_flops <= 0:
        return None
    return 100.0 * c.decode_flops / (tr.program_s(view.decode_program)
                                     * view.peaks.bf16_flops)
