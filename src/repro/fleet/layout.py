"""Pod-aligned target layouts, priced by the postal cost model.

A resize that ignores pod boundaries destroys the two-tier schedule's
locality advantage: a mesh row that straddles a physical pod turns ICI
hops into what the runtime schedules as DCN rounds. So every candidate
layout here keeps each mesh row INSIDE one physical pod — ``per_pod``
divides ``pod_size`` — and :func:`choose_layout` ranks candidates by

1. devices utilized (never leave a whole pod idle), then
2. the modeled two-tier allgather time (:func:`cost_model
   .locality_bruck_model` — Eq. 4, which handles the arbitrary/non-power
   region counts a shrink naturally produces via the allgatherv
   adaptation).

Splitting pods into more, smaller mesh rows (e.g. (6,2) instead of (3,4)
on three 4-chip pods) keeps alignment but multiplies the inter-region
round count, so the cost model rejects it whenever the non-local tier is
the expensive one — exactly the paper's argument, applied to layout
selection instead of schedule selection.

jax is imported lazily (inside :func:`layout_mesh` only): importing this
module never touches jax device state.
"""
from __future__ import annotations

import dataclasses

from repro.core import cost_model


class FleetLayoutError(RuntimeError):
    """A layout could not be built or failed its locality assertion."""


@dataclasses.dataclass(frozen=True, order=True)
class Layout:
    """``pods`` mesh rows of ``per_pod`` devices: mesh shape (q, d)."""

    pods: int
    per_pod: int

    @property
    def total(self) -> int:
        return self.pods * self.per_pod

    @property
    def shape(self) -> tuple[int, int]:
        return (self.pods, self.per_pod)

    def __str__(self) -> str:
        return f"({self.pods}x{self.per_pod})"


def pod_aligned_layouts(capacity: int, pod_size: int) -> list[Layout]:
    """Every layout whose mesh rows nest inside physical ``pod_size``-chip
    pods, using at most ``capacity`` devices. Each whole available pod may
    be split into ``pod_size/d`` rows of ``d`` devices for any divisor
    ``d``; a capacity below one pod degenerates to the flat single-row
    layout (the only shape that wastes nothing)."""
    if capacity < 1 or pod_size < 1:
        return []
    whole_pods = capacity // pod_size
    out = set()
    for q_phys in range(1, whole_pods + 1):
        for d in range(1, pod_size + 1):
            if pod_size % d == 0:
                out.add(Layout(q_phys * (pod_size // d), d))
    if not out:
        out.add(Layout(1, capacity))
    return sorted(out)


def layout_price_s(layout: Layout, *, machine: str = "tpu_multipod",
                   block_bytes: float = 1 << 20) -> float:
    """Modeled worst-rank allgather time for one ``block_bytes`` block per
    rank on this layout (Eq. 4; arbitrary region counts supported)."""
    m = cost_model.MACHINES[machine]
    if layout.pods <= 1:
        return (cost_model.bruck_model(layout.per_pod, block_bytes, m)
                if layout.per_pod > 1 else 0.0)
    if layout.per_pod <= 1:
        # one device per mesh row: no local tier at all — every hop is a
        # non-local round, i.e. the flat Bruck (Eq. 3). (Eq. 4's round
        # simulation needs p_local >= 2 to make progress.)
        return cost_model.bruck_model(layout.total, block_bytes, m)
    return cost_model.locality_bruck_model(
        layout.total, layout.per_pod, block_bytes, m)


def choose_layout(capacity: int, pod_size: int, *,
                  machine: str = "tpu_multipod",
                  block_bytes: float = 1 << 20) -> Layout:
    """The cheapest maximal pod-aligned layout for ``capacity`` devices.

    Utilization dominates (idling a whole pod is never worth a cheaper
    schedule); the cost model breaks ties between equal-device
    arrangements of the same pods. Deterministic: ties after price fall
    back to the fewest mesh rows, then the dataclass order."""
    cands = pod_aligned_layouts(capacity, pod_size)
    if not cands:
        raise FleetLayoutError(
            f"no pod-aligned layout for capacity={capacity} "
            f"pod_size={pod_size}")
    best_total = max(c.total for c in cands)
    maximal = [c for c in cands if c.total == best_total]
    return min(maximal, key=lambda c: (
        layout_price_s(c, machine=machine, block_bytes=block_bytes),
        c.pods, c))


def layout_mesh(layout: Layout, devices=None):
    """Materialize the layout as a ('pod','data') Mesh over the FIRST
    ``layout.total`` devices (devices are pod-major in this simulated
    fleet, so consecutive runs of ``pod_size`` share a pod and each mesh
    row stays pod-local by construction)."""
    import jax
    from repro.launch.mesh import make_mesh

    devs = list(jax.devices() if devices is None else devices)
    if layout.total > len(devs):
        raise FleetLayoutError(
            f"layout {layout} needs {layout.total} devices, "
            f"have {len(devs)}")
    return make_mesh((layout.pods, layout.per_pod), ("pod", "data"),
                     devices=devs[:layout.total])
