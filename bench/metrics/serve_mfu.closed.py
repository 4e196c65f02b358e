"""Share of the chip's bf16 peak in serving: model operations per served
image (work.model_flops_served) times images served in the window, over
window time and peak."""


def read(r):
    if r.model_flops <= 0 or r.window_s <= 0:
        return None
    return 100.0 * r.model_flops / (r.window_s * r.chips
                                    * r.peak["bf16_flops"])
