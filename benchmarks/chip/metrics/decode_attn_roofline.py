"""Kernel kv_decode_attention: sum over its calls of the least time
(costs.decode_attention: live K/V codes and scales only) over the kernel's
device time, in the traced window."""


def read(view):
    tr, c = view.trace, view.costs
    if tr is None or c is None or tr.kernel_s("kv_decode_attention") <= 0 \
            or c.attn_least_s <= 0:
        return None
    return 100.0 * c.attn_least_s / tr.kernel_s("kv_decode_attention")
