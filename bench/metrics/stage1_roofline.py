"""Stage I (collision scoring, ``kernels/collision``): least time of the
traced window's Stage-I work at the chip's peaks over the device time of
its kernel calls (%). In the compiled decode chunk the kernel's
``tpu_custom_call`` is the HLO instruction
``_collision_paged_pallas.<n>``, named after the function jitted around
its ``pallas_call``."""
from bench import counts

KERNEL = ("_collision_paged_pallas",)


def read(run):
    return counts.kernel_roofline(run, "stage1", KERNEL)
