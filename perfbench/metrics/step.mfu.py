"""Operations of a step's forward and backward on one chip, from the shapes,
over the device's busy time for a step in the traced window
(``step.device_ms_per_step``) and the chip's bf16 peak (peaks.json). Idle
time is not in it: ``device.idle_share`` stands beside it, and the rate is
this share times the busy share times the peak over the operations a row."""


def read(ctx):
    t = ctx['trace']
    if t is None or ctx['peak'] is None:
        return None
    flops = ctx['ref'].train_flops_per_row(ctx['cfg']) * ctx['batch'] / ctx['chips']
    return 100.0 * flops * t['steps'] / t['busy_s'] / ctx['peak']['bf16_flops_per_s']
