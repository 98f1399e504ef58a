"""Logical-axis sharding: the single place where parallelism is decided.

Model code annotates arrays with *logical* axis names ("batch", "seq",
"embed", "heads", "kv", "mlp", "vocab", "experts", "layers", ...).  A
:class:`ShardingRules` maps logical names → mesh axes, and is installed as a
context so the same model code runs (a) unsharded on one CPU device, (b) on
the single-pod 16×16 mesh, (c) on the 2×16×16 multi-pod mesh — only the
rules change.

Default production mapping (see DESIGN.md §4):
  batch   → ("pod","data")   data parallel (pod axis = pure DP)
  vocab   → "model"          TP on embedding/lm-head
  heads   → "model"          TP on attention q-heads (padded to multiples)
  mlp     → "model"          TP on FFN hidden
  experts → "model"          EP (expert parallel)
  kv_seq  → "model"          seq-sharded KV cache (flash-decoding style)
  embed   → None  (activations) / "data" for FSDP'd parameters
  seq     → None  (sequence-parallel variants map it to "model")
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ShardingRules", "use_rules", "current_rules", "logical_spec",
           "shard", "named_sharding", "DEFAULT_RULES", "FSDP_RULES",
           "make_2d_device_mesh"]


def make_2d_device_mesh(data_devices: int | None = None,
                        model_devices: int = 1, *,
                        axis_names: tuple[str, str] = ("data", "model"),
                        devices=None) -> Mesh:
    """Validated 2-D (data × model) mesh for the serving engines.

    The data axis shards the lane (batch) tile; the model axis shards
    each layer's output-neuron dimension (weight columns) with spike
    exchange at layer boundaries.  ``data_devices=None`` absorbs every
    device the ``model_devices``-way model axis leaves over, so
    ``make_2d_device_mesh(model_devices=4)`` on an 8-device host yields a
    2×4 mesh.  A ``model_devices=1`` mesh is still built 2-D (a trailing
    1-sized model axis) — the lane partition specs never mention the
    model axis, so every 1-D data-mesh consumer composes unchanged.
    """
    pool = list(jax.devices()) if devices is None else list(devices)
    if len(set(axis_names)) != 2:
        raise ValueError(f"axis_names must be two distinct names, got "
                         f"{axis_names!r}")
    model_devices = int(model_devices)
    if model_devices < 1:
        raise ValueError(f"model_devices={model_devices} must be >= 1")
    if data_devices is None:
        if len(pool) % model_devices:
            raise ValueError(
                f"{len(pool)} devices do not divide over a "
                f"{model_devices}-way model axis — pass data_devices "
                f"explicitly or change the model width")
        data_devices = len(pool) // model_devices
    data_devices = int(data_devices)
    if data_devices < 1:
        raise ValueError(f"data_devices={data_devices} must be >= 1")
    need = data_devices * model_devices
    if need > len(pool):
        raise ValueError(
            f"{data_devices}×{model_devices} (data × model) mesh needs "
            f"{need} devices but only {len(pool)} are visible")
    return jax.make_mesh((data_devices, model_devices), tuple(axis_names),
                         (AxisType.Auto,) * 2, devices=pool[:need])


@dataclass(frozen=True)
class ShardingRules:
    """Mapping logical axis name → mesh axis (str | tuple | None)."""

    rules: dict = field(default_factory=dict)
    axis_sizes: dict = field(default_factory=dict)  # mesh axis → size

    def spec(self, *logical_axes: str | None) -> P:
        return P(*(self.rules.get(a) if a is not None else None
                   for a in logical_axes))

    def ways(self, logical_axis: str | None) -> int:
        """How many shards the resolved mesh axes would create."""
        entry = self.rules.get(logical_axis) if logical_axis else None
        if entry is None:
            return 1
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in axes:
            n *= self.axis_sizes.get(a, 1)
        return n

    def spec_for_shape(self, shape: tuple, *logical_axes) -> P:
        """Like spec(), but drops axes that do not divide the dim."""
        entries = []
        for dim, a in zip(shape, logical_axes):
            w = self.ways(a)
            ok = w > 1 and dim % w == 0
            entries.append(self.rules.get(a) if (a and ok) else None)
        return P(*entries)

    def with_overrides(self, **kw) -> "ShardingRules":
        new = dict(self.rules)
        new.update(kw)
        return ShardingRules(new, self.axis_sizes)


# Production defaults. "batch" resolves to whatever data axes exist; rules
# are built per-mesh by `make_rules` so single- and multi-pod agree.
def make_rules(mesh: Mesh | None, *, fsdp: bool = True,
               sequence_parallel: bool = False) -> ShardingRules:
    if mesh is None:
        return ShardingRules({})
    axes = mesh.axis_names
    data_axes = tuple(a for a in ("pod", "data") if a in axes) or None
    model = "model" if "model" in axes else None
    rules = {
        "batch": data_axes,
        "seq": model if sequence_parallel else None,
        "seq_act": model if sequence_parallel else None,
        "embed": None,
        "heads": model,
        "kv": None,            # kv heads replicated within a TP group
        "head_dim": None,
        "mlp": model,
        "vocab": model,
        "experts": model,
        "expert_cap": data_axes,   # token capacity dim rides the data axes
        "kv_seq": model,       # decode-time KV cache sequence sharding
        "layers": None,
        "conv": None,
        "state": None,
        # parameter-only axes (FSDP shards the non-TP dim of weights):
        "fsdp": ("data" if (fsdp and "data" in axes) else None),
    }
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return ShardingRules(rules, sizes)


DEFAULT_RULES = ShardingRules({})
FSDP_RULES = DEFAULT_RULES  # alias; see make_rules(fsdp=True)

_ctx = threading.local()


@contextlib.contextmanager
def use_rules(rules: ShardingRules):
    prev = getattr(_ctx, "rules", None)
    _ctx.rules = rules
    try:
        yield rules
    finally:
        _ctx.rules = prev


def current_rules() -> ShardingRules | None:
    return getattr(_ctx, "rules", None)


def logical_spec(*logical_axes) -> P:
    rules = current_rules()
    if rules is None:
        return P()
    return rules.spec(*logical_axes)


def shard(x: jax.Array, *logical_axes) -> jax.Array:
    """Annotate activation sharding; no-op outside a rules/mesh context.

    Axes that do not evenly divide the corresponding dim are dropped
    (e.g. "batch" on a global_batch=1 long-context decode).
    """
    rules = current_rules()
    if rules is None or not rules.rules:
        return x
    try:
        spec = rules.spec_for_shape(x.shape, *logical_axes)
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        # No mesh context (e.g. pure-CPU unit test): annotation is advisory.
        return x


def named_sharding(mesh: Mesh, *logical_axes) -> NamedSharding:
    rules = current_rules() or ShardingRules({})
    return NamedSharding(mesh, rules.spec(*logical_axes))
