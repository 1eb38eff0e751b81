"""Kernel ``kernels/decode_attention.py`` (the custom call
``decode_attention``): the least time its calls in the traced window need
(each active slot's query against the K/V of its live positions; the model
module's ``KERNELS``) at the chip's peaks, over the time the kernel ran, in
percent.  The model module says how many calls a decode step makes (one per
attention layer); a model whose layers never call it reads nothing.  Which
bound binds is printed on the traced run's ``roofline_bounds`` line."""
from bench import roofline

KERNEL = "decode_attention"


def read(run):
    return roofline.share(run, KERNEL)
