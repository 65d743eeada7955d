"""Stage II (RSQ-IP rerank, ``kernels/rerank``): least time of the traced
window's Stage-II work at the chip's peaks over the device time of its
kernel calls (%). In the compiled decode chunk the kernel's
``tpu_custom_call`` is the HLO instruction ``_rerank_pallas.<n>``, named
after the function jitted around its ``pallas_call``."""
from bench import counts

KERNEL = ("_rerank_pallas",)


def read(run):
    return counts.kernel_roofline(run, "stage2", KERNEL)
