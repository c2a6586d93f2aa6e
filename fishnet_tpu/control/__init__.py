"""Self-tuning control plane: close the loop from telemetry to knobs.

Every performance-critical knob in the system used to be statically
tuned — coalesce width (probe x EMA at warmup), async pipeline depth,
shed watermarks, prefetch pinning, MCTS leaf-width bounds, DRR tenant
quanta — while the telemetry plane (PRs 7/13/15) measured exactly the
inputs a controller needs and nobody read them back. This package is
the loop closure (doc/control-plane.md):

* :mod:`fishnet_tpu.control.signals` — folds the in-process telemetry
  sources (stage durations via the ``STAGE_OBSERVER`` hook,
  critical-path component attribution, SLO burn rates, cost books,
  coalescer occupancy, shard rungs) into a windowed,
  hysteresis-smoothed :class:`~fishnet_tpu.control.signals
  .ControlSignals` snapshot;
* :mod:`fishnet_tpu.control.actuators` — the typed actuator registry:
  every subsystem exports a BOUNDED, REVERTIBLE setter, and every
  actuation emits ``fishnet_control_actuations_total{knob,direction}``
  plus a ``control`` event span so trace stitching shows why a knob
  moved;
* :mod:`fishnet_tpu.control.controller` — the deterministic
  rule/probe-driven policy behind the :class:`~fishnet_tpu.control
  .controller.Policy` protocol (a learned policy drops in later). No
  wall clock and no randomness on the decision path: decisions are a
  pure function of the signal window.

House gating: ``FISHNET_NO_CONTROL=1`` is the escape hatch — a
constructed controller stops deciding, every actuator refuses to move,
and ``revert()`` restores each subsystem's static default
byte-for-byte. The controller only ever moves SCHEDULING knobs, never
numerics, so analyses stay bit-identical with it on (each knob's own
parity tests pin that, tests/test_coalesce.py and
tests/test_async_dispatch.py among them; no test compares analyses with
the controller running).
"""

from __future__ import annotations

import os

#: Escape hatch (analysis/registry.py R8 row): disables every decision
#: and actuation while leaving construction/wiring inert, so flipping
#: it restores the static defaults byte-for-byte.
NO_CONTROL_ENV = "FISHNET_NO_CONTROL"


def control_enabled() -> bool:
    """Whether the control plane may decide and actuate. One env read
    per control WINDOW (~Hz), not per hot-path operation — the serving
    paths never call this."""
    return os.environ.get(NO_CONTROL_ENV, "0") != "1"


from fishnet_tpu.control.actuators import (  # noqa: E402,F401 - public API
    Actuation,
    Actuator,
    ActuatorRegistry,
)
from fishnet_tpu.control.controller import (  # noqa: E402,F401 - public API
    Action,
    Controller,
    LadderProbe,
    Policy,
    RuleProbePolicy,
)
from fishnet_tpu.control.signals import (  # noqa: E402,F401 - public API
    ControlSignals,
    HysteresisSwitch,
    SignalCollector,
)
