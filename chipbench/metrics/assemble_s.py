"""Host seconds per unit in batched._to_ensemble_result."""

from chipbench.readers import span_mean


def read(run):
    return span_mean(run, "assemble")
