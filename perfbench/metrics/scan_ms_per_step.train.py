"""Device time per train step of the ops under the model's ``blocks``
scope and under none of its block stages (``fft_fwd``, ``mix``,
``fft_inv``, ``bypass``): the scan over the blocks itself, its slicing of
the stacked parameters, the stacking of residuals for the backward pass
and the copies of its carry, forward and backward. Mean over the chips
used, over the steps completed in the window. ms."""
from harness import program

STAGES = ("fft_fwd", "mix", "fft_inv", "bypass")


def read(run):
    return program.mean_ms(
        run, lambda op: (program.under(op.tf_op, "blocks")
                         and not program.under(op.tf_op, *STAGES)), "steps")
