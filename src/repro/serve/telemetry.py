"""Adaptive chunk-length control from live telemetry (the serving control
plane the activity side channel exists for).

Hardware/software co-designs (SparrowSNN; the Bouvier et al. 2020 survey's
activity-monitoring control plane) feed *measured* spike statistics back
into scheduling instead of compile-time guesses.  This module is that
loop's host side: :class:`TelemetryController` consumes per-chunk
:class:`ChunkSummary` observations (reduced from the structured
``core.telemetry.ChunkTelemetry`` record every backend emits) and retunes
one knob between chunk dispatches, the **chunk length** of the next
streaming dispatch.  Lanes that retire mid-chunk burn host-invisible steps
until the chunk ends, so a high observed retirement rate shrinks the chunk
(tighter harvest granularity) while retirement-free steady state grows it
(fewer host syncs per window step).

The knob is *value-neutral by construction*: chunked window execution is
bit-identical under any split (the property tests pin it).  Adaptivity
can therefore never change predictions, retirement steps or energy
counters — only wall-clock.  **Frozen mode** (the default, and what CI
pins) bypasses every observation: the controller returns exactly the
configured chunk length, with zero device syncs.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

import numpy as np

from ..core.telemetry import ChunkTelemetry

__all__ = ["AdaptiveDispatchConfig", "ChunkSummary", "TelemetryController",
           "adaptive_config_from_env", "make_controller", "summarize_chunk"]


@dataclass(frozen=True)
class AdaptiveDispatchConfig:
    """Knobs of the serving telemetry controller.

    ``adaptive=False`` is frozen mode: static chunk length, no telemetry
    readbacks.  The env override ``REPRO_ADAPTIVE_DISPATCH=1`` (see
    :func:`adaptive_config_from_env`) flips the default on for a whole
    run — CI uses it to prove adaptivity is value-neutral across the
    entire suite.
    """

    adaptive: bool = False
    # Chunk-length control: shrink when ≥ shrink_retire_frac of the active
    # lanes retired inside the chunk, grow after grow_patience consecutive
    # retirement-free chunks.
    min_chunk_steps: int = 2
    max_chunk_steps: int = 16
    shrink_retire_frac: float = 0.25
    grow_patience: int = 2


def adaptive_config_from_env() -> AdaptiveDispatchConfig:
    """Default controller config: frozen unless REPRO_ADAPTIVE_DISPATCH=1."""
    on = os.environ.get("REPRO_ADAPTIVE_DISPATCH", "0") == "1"
    return AdaptiveDispatchConfig(adaptive=on)


@dataclass(frozen=True)
class ChunkSummary:
    """Host-side reduction of one chunk's telemetry (plain floats/ints)."""

    density_in: float        # mean input-layer spike density, active steps
    layer_densities: tuple   # per-layer mean input densities
    executed_adds: int       # Σ telemetry adds this chunk (energy channel)
    tiles_skipped: int       # Σ skipped MXU tile pairs this chunk
    lanes_retired: int       # lanes the stability gate froze this chunk
    lanes_active: int        # lanes active when the chunk was dispatched
    active_lane_steps: int   # Σ per-lane steps actually consumed


def summarize_chunk(tel: ChunkTelemetry, layer_sizes, *,
                    steps_before, steps_after,
                    active_before, active_after) -> ChunkSummary:
    """Reduce a chunk's telemetry record to controller observations.

    Densities are occupancy-weighted: frozen lanes contribute zero rows to
    ``n_spk`` AND zero consumed steps, so dividing by the consumed
    lane-steps × fan-in measures the density of the work the device
    actually executed.  Forces a device→host transfer — callers in frozen
    mode skip this entirely (the no-sync guarantee).
    """
    n_spk = np.asarray(tel.n_spk)                    # (chunk, L, B)
    steps_b = np.asarray(steps_before)
    steps_a = np.asarray(steps_after)
    act_b = np.asarray(active_before)
    act_a = np.asarray(active_after)
    lane_steps = int((steps_a - steps_b).sum())
    fan_in = np.asarray(layer_sizes[:-1], np.float64)
    spk_per_layer = n_spk.sum(axis=(0, 2)).astype(np.float64)  # (L,)
    denom = max(1, lane_steps)
    layer_densities = tuple(spk_per_layer / (denom * fan_in))
    tel_adds = n_spk * np.asarray(tel.n_en)
    return ChunkSummary(
        density_in=float(layer_densities[0]),
        layer_densities=layer_densities,
        executed_adds=int(tel_adds.sum()),
        tiles_skipped=int(np.asarray(tel.tiles_skipped).sum()),
        lanes_retired=int(np.logical_and(act_b, ~act_a).sum()),
        lanes_active=int(act_b.sum()),
        active_lane_steps=lane_steps,
    )


@dataclass
class TelemetryController:
    """Chunk-length law driven by the observed retirement rate.

    Deterministic: the decision trajectory is a pure function of the
    observation sequence, so the same traffic replayed gives the same
    chunk lengths (the benchmark records the trajectory as a contract
    artifact).  In frozen mode every property returns the
    static choice and :meth:`observe` is a no-op — bit-for-bit today's
    behavior.
    """

    cfg: AdaptiveDispatchConfig
    static_chunk_steps: int
    num_steps: int
    history: list = field(default_factory=list)
    _chunk: int = 0
    _quiet: int = 0

    def __post_init__(self):
        self._chunk = self.static_chunk_steps

    @property
    def frozen(self) -> bool:
        return not self.cfg.adaptive

    @property
    def chunk_steps(self) -> int:
        """Window steps the next streaming chunk should execute."""
        if self.frozen:
            return self.static_chunk_steps
        return max(1, min(self._chunk, self.num_steps))

    @property
    def min_chunk_steps(self) -> int:
        """Smallest chunk the controller may pick (drive-loop bounds)."""
        if self.frozen:
            return self.static_chunk_steps
        return max(1, min(self.cfg.min_chunk_steps, self.num_steps))

    def observe(self, summary: ChunkSummary) -> None:
        """Fold one chunk's summary into the chunk-length law.

        No-op in frozen mode.
        """
        if self.frozen:
            return
        c = self.cfg
        # chunk-length control from the observed retirement rate
        if summary.lanes_active > 0:
            frac = summary.lanes_retired / summary.lanes_active
            if frac >= c.shrink_retire_frac:
                # proportional shrink: one step at the trigger fraction,
                # one more per additional trigger-width of overshoot — a
                # chunk that retired every lane converges in one
                # observation instead of limping down a step at a time.
                # The clamp bounds are unchanged, and so is the behavior
                # exactly AT the trigger (step 1), which is what keeps
                # the PR 8 speculation-discard guard semantics intact:
                # any retune still lands between chunk dispatches and
                # trips `_spec_steps != controller.chunk_steps`.
                step = 1 + int((frac - c.shrink_retire_frac)
                               / c.shrink_retire_frac)
                self._chunk = max(c.min_chunk_steps, self._chunk - step)
                self._quiet = 0
            elif summary.lanes_retired == 0:
                self._quiet += 1
                if self._quiet >= c.grow_patience:
                    self._chunk = min(c.max_chunk_steps, self._chunk + 1)
                    self._quiet = 0
            else:
                self._quiet = 0
        self.history.append({
            "density_in": summary.density_in,
            "chunk_steps": self.chunk_steps,
            "lanes_retired": summary.lanes_retired,
            "executed_adds": summary.executed_adds,
            "tiles_skipped": summary.tiles_skipped,
        })


def make_controller(cfg_adaptive: AdaptiveDispatchConfig | None, *,
                    chunk_steps: int, num_steps: int) -> TelemetryController:
    """Engine-side constructor: None → the env-resolved default config."""
    return TelemetryController(
        cfg=(adaptive_config_from_env() if cfg_adaptive is None
             else cfg_adaptive),
        static_chunk_steps=chunk_steps,
        num_steps=num_steps)
