"""Host seconds per unit in batched.lower_ensemble."""

from chipbench.readers import span_mean


def read(run):
    return span_mean(run, "lower")
