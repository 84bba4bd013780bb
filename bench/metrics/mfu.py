"""mfu: the whole step's model FLOPs per second over the traced window, as
a share of the chips' bf16 peak. FLOPs from the layer shapes, counted by
the cell's family (`step_flops` of `bench/families/<family>.py`: for the
DCGAN the field's forward and backward passes, each product the field
needs counted once), steps and seconds from the traced window."""


def read(ctx):
    steps, window_s = ctx["steps"], ctx["window_s"]
    if not steps or window_s <= 0:
        return None
    t = ctx["traffic"]
    per_step = ctx["family"].step_flops(ctx["config"], t["batch_per_worker"],
                                        t["workers"])
    peak = ctx["chips"] * ctx["peaks"]["bf16_tflops"] * 1e12
    return 100.0 * per_step * steps / window_s / peak
