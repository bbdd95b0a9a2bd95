"""What the entry drivers share: the spans around the batched engine's
layers and its compile counter."""

from __future__ import annotations

from typing import Dict, List

# label -> module attribute of repro.provisioning.batched that both
# plan_capacity(engine="jax") and run_batched_ensemble(engine="jax") call
LAYER_ATTRS = {"lower": "lower_ensemble", "scan": "run_tick_model",
               "assemble": "_to_ensemble_result"}


def _scan_shapes(model, members, **kw) -> Dict[str, object]:
    """The shapes of one scan call, from its arguments."""
    return dict(N=int(model.n_members), R=int(model.n_rows),
                T=int(model.n_ticks), T60=int(model.occ60.shape[2]),
                S=int(model.n_slots), keep_series=bool(kw["keep_series"]),
                keep_fire=bool(kw["keep_brake_fire"]))


def wrap(spans) -> None:
    """Put a host span around each layer the entry calls."""
    from repro.provisioning import batched

    for label, attr in LAYER_ATTRS.items():
        spans.wrap(batched, attr, label,
                   describe=_scan_shapes if label == "scan" else None)


def trace_count() -> int:
    """Traces of the device program so far (one per compile)."""
    from repro.provisioning import batched

    return batched.jax_trace_count()


def scan_calls(calls: List[dict]) -> List[dict]:
    return [c for c in calls if c["label"] == "scan"]
