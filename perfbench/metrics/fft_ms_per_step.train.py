"""Device time of the FFTs per train step, mean over the chips used. XLA's
TPU backend lowers an FFT to fusions, not to an op of its own, so the ops
are found by their JAX op path: every op under a ``jax.numpy.fft`` call
(``.../jit(fft):``, ``jit(rfft)``, ``jit(irfft)``, ...), forward and
backward, summed over the window and divided by the steps completed in
it. ms."""
import re

FFT = re.compile(r"jit\((i?r?fftn?)\)")


def read(run):
    secs = run.trace.mean_op_seconds(lambda op: FFT.search(op.tf_op))
    return 1e3 * secs / run.counts["steps"]
