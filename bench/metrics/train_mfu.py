"""Whole-step share of the chips' bf16 peak while training: model
operations per image (work.model_flops_train) times images trained in
the window, over window time, chips and peak."""


def read(r):
    if r.model_flops <= 0 or r.window_s <= 0:
        return None
    return 100.0 * r.model_flops / (r.window_s * r.chips
                                    * r.peak["bf16_flops"])
