"""Device time per tick of the ops under the model's ``fft_fwd`` and
``fft_inv`` scopes: the transforms, and the slicing, padding and
transposes around them, of every block. Mean over the chips used, summed
over the window and divided by its ticks. ms."""
from harness import program


def read(run):
    return program.mean_ms(
        run, lambda op: program.under(op.tf_op, "fft_fwd", "fft_inv"), "ticks")
