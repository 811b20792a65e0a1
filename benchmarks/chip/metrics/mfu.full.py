"""Model FLOP utilisation of full-graph training: the operations one
epoch requires (counted from shapes by the model's ``flops_per_epoch``)
times the epochs completed in the traced window, over the window and
the chip's bf16 peak."""


def read(run):
    steps = run.layer.get("steps")
    if not steps or not run.window_s:
        return None
    flops = run.layer["flops_per_step"] * steps
    peak = run.peaks["bf16_flops_per_s"] * len(run.devices)
    return 100.0 * flops / (run.window_s * peak)
