"""Model FLOPs of the real samples trained per second, over the chip's bf16
peak (bench/peaks.json). FLOPs per sample: the configuration's network
module (bench/networks/)."""


def read(run):
    real = sum(r for _, r, _ in run.steps)
    if not real:
        return None
    return 100.0 * real * run.flops_per_sample / run.seconds / run.peak_flops
