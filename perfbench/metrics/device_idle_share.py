"""Share of the traced window in which no op ran on the device: 1 minus
the union of the device's op intervals over the window, mean over the
chips used. %."""


def read(run):
    t = run.trace
    return 100.0 * (1.0 - t.mean_busy_s() / t.window_s)
