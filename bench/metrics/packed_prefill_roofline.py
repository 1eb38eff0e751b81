"""Kernel ``kernels/packed_prefill.py`` (the custom call
``packed_flash_attention``): the least time its calls in the traced window
need (each packed segment's causal span, unpadded; the model module's
``KERNELS``) at the chip's peaks, over the time the kernel ran, in percent.
The model module says how many calls an admission makes; an admission whose
program ran no kernel (a pack under the program's short-prefill rule) is
left out, and a model whose layers never call it reads nothing.  Which
bound binds is printed on the traced run's ``roofline_bounds`` line."""
from bench import roofline

KERNEL = "packed_flash_attention"


def read(run):
    return roofline.share(run, KERNEL)
