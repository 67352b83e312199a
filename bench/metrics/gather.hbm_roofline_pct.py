"""Share of the HBM roofline reached by the served gather, in %: twice
the union bytes the windows answered over the device time of the
gather's ops in the trace, against peak HBM bandwidth.  The served
gather is ``jnp.take`` (XLA module ``jit__take``)."""

from harness.readers import roofline_pct

GATHER_MODULES = r"^jit__take\("


def read(window):
    return roofline_pct(window, GATHER_MODULES)
