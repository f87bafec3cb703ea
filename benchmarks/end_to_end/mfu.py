"""Operations the forward and backward pass need per image (from shapes,
``ops_count/``) x images/s/chip / the device kind's bf16 peak."""


def read(run):
    return (run.flops_per_image * run.images_per_s_per_chip
            / run.peak["bf16_flops_per_s"])
