"""Whole step / device: images' worth of denoising completed per second
over the whole rounds of the traced window (``harness.image_rate``), times
the FLOPs one image requires (``flops.image_flops``), over the chip's bf16
peak, in percent."""


def read(run):
    if not run.get("traced_window") or not run["peaks"]:
        return None
    rate = run["image_rate"](*run["traced_window"])
    if not rate:
        return None
    return 100.0 * rate * run["image_flops"] / run["peaks"]["bf16_flops_per_s"]
