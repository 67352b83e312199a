"""Share of the traced window in which no op ran on the device, in %."""

from harness.readers import idle_pct


def read(window):
    return idle_pct(window)
