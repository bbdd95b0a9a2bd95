#!/usr/bin/env python
"""Generate docs/scenarios.md and docs/registries.md from the live
registries.

Every named scenario (``table2-*``, ``fig*``, ``cluster-*``, ``mc-*``,
``fleet-*``, ``fleet-rebalance-*``, ``site-*``, ``chaos-*``) is rendered
into one scenario reference table, and every pluggable-component registry —
policies, routers, admission controllers, rebalance policies, occupancy
generators, chaos fault events, alert rules — into a registry reference, so the docs cannot drift from the code: a tier-1
test regenerates both files in memory and asserts they match what is checked
in, and ``--check`` does the same from the command line (wired into
``tools/smoke.sh`` / CI).

  PYTHONPATH=src python tools/gen_scenario_docs.py          # rewrite both
  PYTHONPATH=src python tools/gen_scenario_docs.py --check  # verify only
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

DOC_PATH = os.path.join(os.path.dirname(__file__), "..", "docs", "scenarios.md")
REG_PATH = os.path.join(os.path.dirname(__file__), "..", "docs", "registries.md")

HEADER = """\
# Scenario reference

<!-- GENERATED FILE — do not edit by hand.
     Regenerate with: PYTHONPATH=src python tools/gen_scenario_docs.py
     A tier-1 test (tests/test_docs.py) asserts this file matches the
     registry; tools/smoke.sh runs the same check before merge. -->

Every experiment in this repo is a named, JSON-serializable
[`Scenario`](architecture.md) in a process-wide registry
(`repro.experiments.get_scenario`). Benchmarks, tests, and the examples
share these exact configurations; variants derive from them with
`with_()` / `with_fleet()` / `with_policy()` / `with_routing()` /
`with_controller()` / `with_hierarchy()`.

Run any scenario end to end with:

```python
from repro.experiments import get_scenario, run_experiment
import repro.provisioning  # registers the mc-* generator families

outcome = run_experiment(get_scenario("fleet-rebalance-predictive"))
```

| scenario | duration | fleet | traffic | policy | routing | controller | budget | faults | alerts |
|---|---|---|---|---|---|---|---|---|---|
"""

FOOTER = """
**Column notes.** *fleet* is `n_rows x n_servers` actually hosted
(`n_provisioned x (1 + added_frac)` per row); a trailing `derated` marks
heterogeneous per-row budgets (`FleetSpec.row_budget_fracs`), and a
`tree AxBxC` marks an explicit power-budget hierarchy
(`HierarchySpec.shape`, root-down fan-outs; `!path` lists derated interior
nodes; `rated a/b/c MW` the interior levels' ratings root-down,
`HierarchySpec.level_capacity_w`, `-` for a level rated at its budget).
*traffic* names the occupancy generator and its peak busy-server
fraction. *routing* is `router/admission` for fleet scenarios (empty for
pre-baked per-row traces). *controller* is the power-rebalancing policy
(`ControllerSpec.kind`, with its rebalance interval and — when not the
per-rack default — its scope) for dynamically rebalanced fleets. *budget*
is the row power envelope rule: `calibrated` (Table-2 79%-peak operating
point), `nominal` (n_provisioned x server rating), or explicit watts.
*faults* is the scenario's injected chaos timeline (`Scenario.faults`),
one `kind@t` entry per `FaultEvent` (`none` marks an explicitly attached
empty `FaultSpec` — the bit-parity anchor); empty means no fault engine at
all. *alerts* is the scenario's attached alert pack (`Scenario.alerts`):
`default (n)` for the stock `default_alert_pack()`, otherwise one entry
per `AlertSpec` kind; empty means no alert engine (the evaluator is
write-only either way — alerts never perturb the simulation).
"""

REG_HEADER = """\
# Registry reference

<!-- GENERATED FILE — do not edit by hand.
     Regenerate with: PYTHONPATH=src python tools/gen_scenario_docs.py
     A tier-1 test (tests/test_docs.py) asserts this file matches the
     live registries; tools/smoke.sh runs the same check before merge. -->

Every pluggable component is registered by name so scenarios stay
JSON-serializable: a [`Scenario`](scenarios.md) names a policy, router,
admission controller, rebalance policy, occupancy generator — and, for
chaos scenarios, fault-event kinds and alert-rule kinds — and the
builders below construct fresh instances per run. The one-line summaries
are the first line of each implementation's docstring.
"""

REG_FOOTER = """
**Where they plug in.** *policies* consume per-row `Telemetry` samples and
emit frequency-cap commands (`PolicySpec.kind`). *routers* place each
admitted request on a row (`RoutingSpec.router`); *admission controllers*
decide first whether it runs at all (`RoutingSpec.admission`). *rebalance
policies* re-divide power envelopes across the budget hierarchy
(`ControllerSpec.kind`, with `scope` = `rack` | `cluster` | `tree` — the
latter recursing over every interior node of the scenario's
`HierarchySpec`). *occupancy generators* produce the seeded busy-server
curves traffic is sampled from (`TrafficSpec.generator`). *fault events*
are the `FaultEvent.kind` values a `FaultSpec` timeline may carry
(`Scenario.faults`); the `ChaosInjector` applies them between telemetry
ticks and logs every application to `FleetResult.fault_events`. *alert
rules* are the `AlertSpec.kind` values a scenario's alert pack may carry
(`Scenario.alerts`); the `AlertEngine` evaluates them per telemetry tick
and emits `alert_engage`/`alert_release` events without perturbing the
run (`repro.obs.alerts`).
"""


def _fmt_duration(s: float) -> str:
    day = 86_400.0
    if s % (7 * day) == 0:
        return f"{int(s // (7 * day))} w"
    if s % day == 0:
        return f"{int(s // day)} d"
    if s % 3600.0 == 0:
        return f"{int(s // 3600.0)} h"
    hours = f"{s / 3600.0:.2f}".rstrip("0").rstrip(".")
    return f"{hours} h"


def _fmt_fleet(sc) -> str:
    f = sc.fleet
    txt = f"{f.n_rows} x {f.n_servers}"
    if f.added_frac:
        txt += f" (+{f.added_frac:.0%})"
    if f.row_budget_fracs is not None:
        txt += " derated"
    h = getattr(sc, "hierarchy", None)
    if h is not None:
        txt += " tree" + "x".join(str(s) for s in h.shape)
        for path in sorted(h.budget_fracs):
            txt += f" !{path}"
        if h.level_capacity_w is not None:
            txt += " rated " + "/".join(
                "-" if r is None else f"{r / 1e6:g}"
                for r in h.level_capacity_w) + " MW"
    return txt


def _fmt_traffic(sc) -> str:
    t = sc.traffic
    txt = f"{t.generator} @{t.occ_peak:.2f}"
    if t.priority_mix_override is not None:
        txt += f" hp={t.priority_mix_override:.2f}"
    return txt


def _fmt_routing(sc) -> str:
    r = sc.routing
    if r is None:
        return ""
    return r.router if r.admission == "admit-all" else f"{r.router}/{r.admission}"


def _fmt_controller(sc) -> str:
    c = getattr(sc, "controller", None)
    if c is None:
        return ""
    txt = f"{c.kind} @{c.interval_s:.0f}s"
    if c.scope != "rack":
        txt += f" {c.scope}"
    return txt


def _fmt_budget(sc) -> str:
    if isinstance(sc.budget, str):
        return sc.budget
    return f"{sc.budget:.0f} W"


def _fmt_faults(sc) -> str:
    fs = getattr(sc, "faults", None)
    if fs is None:
        return ""
    if fs.is_noop:
        return "none"
    return " ".join(f"`{e.kind}@{e.t:.0f}s`" for e in fs.events)


def _fmt_alerts(sc) -> str:
    from repro.obs.alerts import default_alert_pack
    alerts = getattr(sc, "alerts", None)
    if alerts is None:
        return ""
    if tuple(alerts) == default_alert_pack():
        return f"default ({len(alerts)})"
    return " ".join(f"`{s.kind}`" for s in alerts)


def generate() -> str:
    """The full docs/scenarios.md contents for the current registry."""
    import repro.provisioning  # noqa: F401  (registers mc-* scenarios)
    from repro.experiments import get_scenario, list_scenarios

    rows = []
    for name in list_scenarios():
        sc = get_scenario(name)
        rows.append(
            f"| `{name}` | {_fmt_duration(sc.duration_s)} | {_fmt_fleet(sc)} "
            f"| {_fmt_traffic(sc)} | {sc.policy.kind} | {_fmt_routing(sc)} "
            f"| {_fmt_controller(sc)} | {_fmt_budget(sc)} "
            f"| {_fmt_faults(sc)} | {_fmt_alerts(sc)} |")
    return HEADER + "\n".join(rows) + "\n" + FOOTER


def _summary(obj) -> str:
    """First docstring line of a registered implementation (builders that
    are classes document themselves; partials/functions likewise)."""
    doc = getattr(obj, "__doc__", None) or ""
    first = doc.strip().splitlines()[0].strip() if doc.strip() else ""
    return first


def _registry_table(title: str, intro: str, entries) -> str:
    lines = [f"## {title}", "", intro, "",
             "| name | implementation | summary |", "|---|---|---|"]
    for name, obj in entries:
        impl = getattr(obj, "__name__", type(obj).__name__)
        lines.append(f"| `{name}` | `{impl}` | {_summary(obj)} |")
    return "\n".join(lines) + "\n"


def generate_registries() -> str:
    """The full docs/registries.md contents for the current registries."""
    import repro.provisioning  # noqa: F401  (registers the mc-* generators)
    from repro.chaos import FAULT_EVENT_BUILDERS
    from repro.core.traces import get_occupancy_generator, list_occupancy_generators
    from repro.obs.alerts import ALERT_BUILDERS
    from repro.experiments.scenario import POLICY_BUILDERS
    from repro.fleet.controller import REBALANCE_BUILDERS
    from repro.fleet.router import ADMISSION_BUILDERS, ROUTER_BUILDERS

    sections = [
        _registry_table(
            "Capping policies (`PolicySpec.kind`)",
            "Per-row power-management policies consuming 2 s `Telemetry` "
            "samples (`repro.core.policy`).",
            sorted(POLICY_BUILDERS.items())),
        _registry_table(
            "Routers (`RoutingSpec.router`)",
            "Fleet dispatch policies scoring `RowView` snapshots per arrival "
            "(`repro.fleet.router`).",
            sorted(ROUTER_BUILDERS.items())),
        _registry_table(
            "Admission controllers (`RoutingSpec.admission`)",
            "Fleet-door shedding policies consulted before routing "
            "(`repro.fleet.router`).",
            sorted(ADMISSION_BUILDERS.items())),
        _registry_table(
            "Rebalance policies (`ControllerSpec.kind`)",
            "Budget-division policies the `FleetController` runs per rack, "
            "per cluster, or recursively per hierarchy node "
            "(`repro.fleet.controller`).",
            sorted(REBALANCE_BUILDERS.items())),
        _registry_table(
            "Occupancy generators (`TrafficSpec.generator`)",
            "Seeded busy-server-curve families behind the trace generators "
            "(`repro.core.traces`, `repro.provisioning.ensembles`).",
            [(n, get_occupancy_generator(n))
             for n in list_occupancy_generators()]),
        _registry_table(
            "Fault events (`FaultEvent.kind`)",
            "Chaos-timeline event kinds the `ChaosInjector` applies to a "
            "running fleet between telemetry ticks (`repro.chaos`).",
            sorted(FAULT_EVENT_BUILDERS.items())),
        _registry_table(
            "Alert rules (`AlertSpec.kind`)",
            "Streaming alert rules the `AlertEngine` evaluates per "
            "telemetry tick, with hysteresis and engage-streak debouncing "
            "(`repro.obs.alerts`).",
            sorted(ALERT_BUILDERS.items())),
    ]
    return REG_HEADER + "\n" + "\n".join(sections) + REG_FOOTER


def _targets():
    return [(os.path.normpath(DOC_PATH), generate),
            (os.path.normpath(REG_PATH), generate_registries)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="exit 1 if docs/scenarios.md or docs/registries.md "
                         "is out of sync")
    args = ap.parse_args()
    rc = 0
    for path, gen in _targets():
        text = gen()
        if args.check:
            try:
                with open(path) as fh:
                    on_disk = fh.read()
            except FileNotFoundError:
                print(f"missing {path}; run tools/gen_scenario_docs.py")
                rc = 1
                continue
            if on_disk != text:
                print(f"{path} is out of sync with the live registries; "
                      "run: PYTHONPATH=src python tools/gen_scenario_docs.py")
                rc = 1
            else:
                print(f"{path} in sync ({len(text.splitlines())} lines)")
            continue
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as fh:
            fh.write(text)
        print(f"wrote {path}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
