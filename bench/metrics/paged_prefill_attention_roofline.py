"""Share of its roofline that the paged attention kernel reached in the
traced window: the useful work of the positions processed there
(``work/paged_prefill_attention.py``) at the chip's peaks, over the
kernel's device time. Which bound limits it is printed beside it."""
LAYER, UNIT, MOVES = "attention kernels", "%", "ttft_p90_ms"
KERNEL = "paged_prefill_attention"


def read(ctx):
    return ctx.roofline(KERNEL)
