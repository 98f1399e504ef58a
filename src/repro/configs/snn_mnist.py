"""snn-mnist — the paper's own model (Poisson-encoded LIF classifier).

Not an LM: 784→10 fully connected LIF layer, 20-timestep window, 8-bit
weights (9-bit signed codes), shift-4 decay (β = 1/16), threshold 128.
Registered so ``--arch snn-mnist`` selects it in the launchers; the 40
dry-run cells are the 10 LM archs — this config is exercised by the paper
benchmarks and its own batch-parallel dry-run entry.
"""

from __future__ import annotations

from dataclasses import dataclass

from ..core.layers import ConvPool, Dense
from ..core.lif import LIFConfig
from ..core.snn import SNNConfig
from .base import ArchConfig
from .registry import register

# LM-shaped registry entry (family "snn") so arch listings include it.
CONFIG = register(ArchConfig(
    name="snn-mnist", family="snn",
    num_layers=1, d_model=784, num_heads=1, num_kv_heads=1,
    head_dim=1, d_ff=0, vocab_size=10,
    optimizer="adamw", remat=False, scan_layers=False,
))

# The real configuration object used by the SNN engine.  ``backend`` picks
# the integer-engine realisation (fused megakernel | staged Pallas kernels |
# pure-jnp reference); "auto" resolves to fused on TPU, reference on CPU.
SNN_CONFIG = SNNConfig(
    layer_sizes=(784, 10),
    num_steps=20,
    lif=LIFConfig(decay_shift=4, v_threshold=128, v_rest=0),
    weight_bits=8,
    qat=True,
    readout="count",
    active_pruning=False,
    backend="auto",
)

SNN_CONFIG_PRUNED = SNNConfig(
    layer_sizes=(784, 10),
    num_steps=20,
    lif=LIFConfig(decay_shift=4, v_threshold=128, v_rest=0),
    weight_bits=8,
    qat=True,
    readout="first_spike",
    active_pruning=True,
    backend="auto",
)

# Streaming-serving mesh knobs (serve.ShardedSNNStreamEngine).  The lane
# tile is data-parallel: ``axis_name`` shards the batch axis of every
# LaneState leaf.  ``model_devices > 1`` adds a second mesh axis
# (``model_axis_name``) that shards each layer's output-neuron weight
# columns across devices (spike exchange at layer boundaries) — the 2-D
# (data × model) mesh that keeps WIDE-class stacks VMEM-resident.
# ``num_devices=None`` lets the data axis absorb every device the model
# axis leaves over; the engine asserts divisibility.
@dataclass(frozen=True)
class SNNStreamMeshConfig:
    axis_name: str = "data"
    num_devices: int | None = None     # data-axis width (None = the rest)
    model_axis_name: str = "model"
    model_devices: int = 1             # model-axis width (1 = pure data)
    # None defers to the engine: a dispatch-cache hit supplies the tuned
    # value, otherwise the historical defaults (8 lanes, 4-step chunks).
    lanes_per_device: int | None = None  # slots per DATA-axis device block
    chunk_steps: int | None = None     # window steps per device dispatch
    overlap: bool = True               # speculative chunk k+1 dispatch
    # Telemetry-driven dispatch tuning (serve.telemetry): None reads the
    # REPRO_ADAPTIVE_DISPATCH env default — frozen (static threshold +
    # chunk length, zero readbacks) unless the env flips it on.  Adaptive
    # mode is value-neutral: it only moves performance-facing knobs.
    adaptive: "AdaptiveDispatchConfig | None" = None
    # Persisted autotuner output (repro.tune): a DispatchCache instance, a
    # path to the versioned JSON file, or None to read REPRO_DISPATCH_CACHE
    # (False disables even the env).  Tuned shapes fill the None knobs
    # above; explicit knob values always win.
    dispatch_cache: "object | None" = None


SNN_STREAM_MESH = SNNStreamMeshConfig()

# Priority classes of the serving tier, ordered lowest → highest: under
# overload the router sheds from the left (batch work is the first to
# go), deadline admission applies to every class equally.  Deployments
# that need more tiers replace the tuple wholesale — the router treats it
# as an ordered vocabulary, nothing is hard-coded to these three names.
TIER_PRIORITY_CLASSES = ("batch", "standard", "interactive")


# Serving-tier knobs (serve.SNNServingTier): the fleet front end that
# sprays requests across ``num_engines`` per-host engines, applies the
# SLO admission policy, and drives zero-drain weight rollouts.  Deadlines
# are in window steps (the currency of RequestResult.steps); ``None``
# means the class of requests carries no deadline and is never
# deadline-shed.  ``queue_limit`` caps each engine's host queue — the
# overload boundary where lowest-priority-first shedding starts;
# ``None`` queues without bound (and only deadline shedding applies).
@dataclass(frozen=True)
class SNNServingTierConfig:
    num_engines: int = 2
    # None defers to the per-engine dispatch-cache decision (tuned shapes
    # on a hit, the historical 8-lane / 4-step defaults otherwise).
    lanes_per_engine: int | None = None
    chunk_steps: int | None = None
    priority_classes: tuple = TIER_PRIORITY_CLASSES
    default_priority: str = "standard"
    default_deadline_steps: int | None = None
    queue_limit: int | None = 64
    shedding: bool = True
    # sharded=True carves the visible devices into num_engines contiguous
    # slices — each engine is a ShardedSNNStreamEngine over its own mesh
    # (a simulated per-host lane mesh; CI runs two 4-device hosts).
    sharded: bool = False
    devices_per_engine: int | None = None
    adaptive: "AdaptiveDispatchConfig | None" = None
    # Fault tolerance (serve.faults): ``fault_plan`` arms a deterministic
    # injection schedule (a FaultPlan, or the compact env-spec string
    # "seed=11,dispatch=0.03"); None leaves engines to arm from the
    # REPRO_FAULT_PLAN env, and injection-free otherwise.  ``fault_cfg``
    # tunes the recovery policy (retry budget, backoff, demotion /
    # promotion thresholds, watchdog deadline, quarantine count); None
    # uses FaultToleranceConfig defaults.
    fault_plan: "FaultPlan | str | None" = None
    fault_cfg: "FaultToleranceConfig | None" = None
    # Persisted autotuner output (repro.tune), threaded to every engine in
    # the fleet: DispatchCache | path | None (env REPRO_DISPATCH_CACHE) |
    # False (disabled).  Per-engine hit/miss decisions are recorded on
    # ``SNNServingTier.cache_decisions``.
    dispatch_cache: "object | None" = None
    # Recovery knobs, exposed individually so deployments tune them
    # without constructing a FaultToleranceConfig by hand.  ``None``
    # keeps the FaultToleranceConfig default; any non-None value is
    # folded into the config built by :meth:`resolve_fault_cfg` (which
    # also runs the validation: every count >= 1, retries/respawns >= 0,
    # heartbeat_deadline_s > heartbeat_interval_s > 0).  Setting any of
    # these alongside an explicit ``fault_cfg`` is a configuration
    # conflict and raises — one source of truth per deployment.
    watchdog_chunks: int | None = None
    max_retries: int | None = None
    backoff_base: int | None = None
    backoff_max: int | None = None
    demote_after: int | None = None
    promote_after: int | None = None
    fail_after: int | None = None
    quarantine_after: int | None = None
    heartbeat_interval_s: float | None = None
    heartbeat_deadline_s: float | None = None
    max_respawns: int | None = None

    _KNOB_FIELDS = ("watchdog_chunks", "max_retries", "backoff_base",
                    "backoff_max", "demote_after", "promote_after",
                    "fail_after", "quarantine_after",
                    "heartbeat_interval_s", "heartbeat_deadline_s",
                    "max_respawns")

    def resolve_fault_cfg(self):
        """The effective FaultToleranceConfig: ``fault_cfg`` verbatim, or
        one built from the individual knob overrides (validated by the
        FaultToleranceConfig constructor)."""
        overrides = {k: getattr(self, k) for k in self._KNOB_FIELDS
                     if getattr(self, k) is not None}
        if self.fault_cfg is not None:
            if overrides:
                raise ValueError(
                    f"SNNServingTierConfig sets both fault_cfg and the "
                    f"individual recovery knobs {sorted(overrides)} — "
                    f"pick one source of truth (put the values in the "
                    f"fault_cfg, or drop it and use the knobs)")
            return self.fault_cfg
        if not overrides:
            return None
        from ..serve.faults import FaultToleranceConfig
        return FaultToleranceConfig(**overrides)

    def __post_init__(self):
        # eager validation: a bad knob combination fails at config
        # construction, not at first tier/cluster build
        self.resolve_fault_cfg()


SNN_SERVING_TIER = SNNServingTierConfig()


def make_serving_tier(params_q: dict, snn_cfg: SNNConfig = SNN_CONFIG,
                      knobs: SNNServingTierConfig = SNN_SERVING_TIER,
                      **tier_kw):
    """Build a ``serve.SNNServingTier`` from the knobs — the deployment
    surface for the fleet front end, mirroring ``make_stream_engine``."""
    from ..serve import SNNServingTier
    return SNNServingTier(
        params_q, snn_cfg, num_engines=knobs.num_engines,
        lanes_per_engine=knobs.lanes_per_engine,
        chunk_steps=knobs.chunk_steps,
        priority_classes=knobs.priority_classes,
        default_priority=knobs.default_priority,
        default_deadline_steps=knobs.default_deadline_steps,
        queue_limit=knobs.queue_limit, shedding=knobs.shedding,
        sharded=knobs.sharded,
        devices_per_engine=knobs.devices_per_engine,
        adaptive=knobs.adaptive, fault_plan=knobs.fault_plan,
        fault_cfg=knobs.resolve_fault_cfg(),
        dispatch_cache=knobs.dispatch_cache, **tier_kw)


# Process-level cluster knobs (serve.ClusterCoordinator): the failover
# tier above the in-process serving tier — ``num_workers`` engine
# subprocesses supervised over heartbeat RPC, lane checkpoints shipped
# every round, accounting write-ahead to ``ledger_dir``.  The recovery
# policy (heartbeat interval/deadline, respawn budget) comes from the
# tier knobs' resolve_fault_cfg() via make_cluster.
@dataclass(frozen=True)
class SNNClusterConfig:
    num_workers: int = 2
    lanes_per_worker: int = 4
    chunk_steps: int = 4
    backend: str | None = None
    fault_plan: "FaultPlan | str | None" = None
    ledger_dir: str | None = None      # required at build time

    def __post_init__(self):
        if self.num_workers < 1:
            raise ValueError(
                f"num_workers must be >= 1, got {self.num_workers}")
        if self.lanes_per_worker < 1:
            raise ValueError(
                f"lanes_per_worker must be >= 1, got "
                f"{self.lanes_per_worker}")


SNN_CLUSTER = SNNClusterConfig()


def make_cluster(params_q: dict, snn_cfg: SNNConfig = SNN_CONFIG,
                 knobs: SNNClusterConfig = SNN_CLUSTER,
                 tier_knobs: SNNServingTierConfig = SNN_SERVING_TIER,
                 **cluster_kw):
    """Build a ``serve.ClusterCoordinator`` from the knobs.

    The recovery policy threads through ``tier_knobs.resolve_fault_cfg()``
    — the same validated source the in-process tier uses, so heartbeat /
    respawn / watchdog settings are configured once for both paths.
    """
    from ..serve import ClusterCoordinator
    cluster_kw.setdefault("ledger_dir", knobs.ledger_dir)
    return ClusterCoordinator(
        params_q, snn_cfg, num_workers=knobs.num_workers,
        lanes_per_worker=knobs.lanes_per_worker,
        chunk_steps=knobs.chunk_steps, backend=knobs.backend,
        fault_plan=knobs.fault_plan,
        fault_cfg=tier_knobs.resolve_fault_cfg(),
        **cluster_kw)


def make_stream_mesh(knobs: SNNStreamMeshConfig = SNN_STREAM_MESH):
    """Build the serving lane mesh from the knobs.

    ``model_devices == 1`` keeps the historical 1-D data mesh;
    ``model_devices > 1`` builds the validated 2-D (data × model) mesh.
    """
    import jax

    if knobs.model_devices > 1:
        from ..distributed.sharding import make_2d_device_mesh
        return make_2d_device_mesh(
            data_devices=knobs.num_devices,
            model_devices=knobs.model_devices,
            axis_names=(knobs.axis_name, knobs.model_axis_name))
    from jax.sharding import AxisType
    n = knobs.num_devices or len(jax.devices())
    return jax.make_mesh((n,), (knobs.axis_name,), (AxisType.Auto,),
                         devices=jax.devices()[:n])


def make_stream_engine(params_q: dict, snn_cfg: SNNConfig = SNN_CONFIG,
                       knobs: SNNStreamMeshConfig = SNN_STREAM_MESH,
                       **engine_kw):
    """Build a ``serve.ShardedSNNStreamEngine`` from the mesh knobs — the
    one place a deployment configures the lane mesh (knob changes flow
    through here; constructing the engine directly bypasses them)."""
    from ..serve import ShardedSNNStreamEngine
    return ShardedSNNStreamEngine(
        params_q, snn_cfg, mesh=make_stream_mesh(knobs),
        axis_name=knobs.axis_name,
        model_axis_name=knobs.model_axis_name,
        lanes_per_device=knobs.lanes_per_device,
        chunk_steps=knobs.chunk_steps, overlap=knobs.overlap,
        adaptive=knobs.adaptive,
        dispatch_cache=knobs.dispatch_cache, **engine_kw)


# Hidden-layer stack (beyond the paper's topology): exercises the
# multi-layer fused megakernel — inter-layer spike traffic stays on-chip,
# which is where staged execution pays 2·T·B·N HBM bytes per hop.
SNN_CONFIG_DEEP = SNNConfig(
    layer_sizes=(784, 128, 64, 10),
    num_steps=20,
    lif=LIFConfig(decay_shift=4, v_threshold=128, v_rest=0),
    weight_bits=8,
    qat=True,
    readout="count",
    active_pruning=False,
    backend="auto",
)

# Widened SNN_CONFIG_DEEP whose int8-packed resident footprint
# (~13.5 MiB by kernels.fused_snn.stack_vmem_bytes for the padded
# 896→2048→2048→128 stack — the packed weights alone are 12 MiB) exceeds
# the fused kernel's VMEM residency budget: single-device ``auto`` on TPU
# resolves it to the ``fused_streamed`` backend — weights stay in HBM and
# are double-buffered through VMEM slab scratch, still ONE launch per
# chunk — and an explicit single-device ``fused`` request raises.  On a
# 4-way model axis (``resolve_backend(..., model_shards=4)``) each device
# holds only its 2048/4-column weight shard (~3.4 MiB), the per-shard
# footprint fits the budget, and ``auto`` resolves to VMEM-resident
# ``fused`` — the stack the 2-D (data × model) mesh exists for.
SNN_CONFIG_WIDE = SNNConfig(
    layer_sizes=(784, 2048, 2048, 10),
    num_steps=20,
    lif=LIFConfig(decay_shift=4, v_threshold=128, v_rest=0),
    weight_bits=8,
    qat=True,
    readout="count",
    active_pruning=False,
    backend="auto",
)

# The convolutional SNN 12C5-MP2-64C5-MP2-1024FC10 (Eshraghian et al.,
# "Training Spiking Neural Networks Using Lessons From Deep Learning",
# Proc. IEEE 2023; snnTorch tutorial 6) on the paper's integer LIF
# datapath: 28x28 pixels, a 5x5 conv to 12 channels and a 2x2 max-pool of
# the currents (12x12x12 LIF), a 5x5 conv to 64 and a 2x2 max-pool
# (64x4x4 LIF), a fully connected 1024->10 LIF layer.  Bias-free, as the
# paper's datapath.  1,411,840 synaptic MACs a lane step over 29,740
# weight codes; resident in the fused stack kernel (layer_sizes derive to
# 784-1728-1024-10).
SNN_CONFIG_CONV = SNNConfig(
    layers=(ConvPool(c_out=12, in_shape=(1, 28, 28), kernel=5, pool=2),
            ConvPool(c_out=64, in_shape=(12, 12, 12), kernel=5, pool=2),
            Dense(10)),
    num_steps=20,
    lif=LIFConfig(decay_shift=4, v_threshold=128, v_rest=0),
    weight_bits=8,
    qat=False,
    readout="count",
    active_pruning=False,
    backend="auto",
)
