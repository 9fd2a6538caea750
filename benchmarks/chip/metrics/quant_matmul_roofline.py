"""Kernel quant_matmul: sum over its calls of the least time (the larger of
FLOPs / bf16 peak and bytes / HBM bandwidth, costs.quant_matmul) over the
kernel's device time, in the traced window."""


def read(view):
    tr, c = view.trace, view.costs
    if tr is None or c is None or tr.kernel_s("quant_matmul") <= 0 \
            or c.qmm_least_s <= 0:
        return None
    return 100.0 * c.qmm_least_s / tr.kernel_s("quant_matmul")
