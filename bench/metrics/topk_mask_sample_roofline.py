"""Share of its roofline that the sampling kernel reached in the traced
window: one logits row per sampled position (``work/topk_mask_sample.py``)
at the chip's peaks, over the kernel's device time."""
LAYER, UNIT, MOVES = "sampling kernel", "%", "itl_p50_ms"
KERNEL = "topk_mask_sample"


def read(ctx):
    return ctx.roofline(KERNEL)
