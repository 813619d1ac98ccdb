"""Model FLOPs per step (bench/flops.py, from the config's shapes) times
steps per second of the window, over chips times the chip's bf16 peak."""


def read(rec):
    if rec.peaks is None:
        return None
    rate = rec.flops_per_step * rec.window_steps / rec.window_s
    return 100.0 * rate / (rec.chips * rec.peaks["bf16_flops_per_s"])
