"""Logical-axis sharding rules → GSPMD shardings.

Model code annotates parameters and activations with *logical* axis names;
this module resolves them against whatever mesh is active (single CPU device,
the 256-chip pod, or the 2×16×16 two-pod mesh).  Resolution silently drops an
axis when the dimension is not divisible by the mesh-axis extent (e.g. 40
query heads on a 16-way "model" axis, or 8 KV heads) — the tensor is then
replicated along that mesh axis, which is always correct, and the roofline
harness reports the resulting collective traffic.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.obs.telemetry import get_telemetry

# logical name -> candidate mesh axes (in priority order; tuples mean "use all
# that exist, jointly")
DEFAULT_RULES = {
    None: None,
    "replicated": None,
    "layers": None,
    "batch": ("pod", "data"),          # data parallel axis (both pods)
    "seq": ("model",),                 # Megatron-SP sequence sharding
    "vocab": ("model",),
    "heads": ("model",),               # flattened (H*dh) or head axis
    # head axis of the attention output entering wo: training keeps it on
    # "model" (a row-parallel wo, merged by psum); serving has no rule for
    # it, so the heads are all-gathered first and wo sees whole rows
    "attn_out": ("model",),
    "kv": ("model",),
    "ff": ("model",),
    "expert": ("model",),
    "inner": ("model",),               # mamba d_inner / ssd heads
    "fsdp": ("pod", "data"),           # ZeRO-3 style weight shard (big archs)
    "embed": None,                     # d_model of activations
    # decode KV-cache sequence axis: split-K over "model" (flash-decoding
    # analogue); falls through to "data" when batch=1 frees it (long_500k)
    "kvseq": ("model", "data"),
}


class Sharder:
    """Resolves logical axis tuples to NamedShardings for one mesh.

    ``fsdp=False`` maps the "fsdp" logical axis to None (weights replicated
    across data);  ``seq_shard=False`` disables activation sequence sharding.
    """

    def __init__(self, mesh: Optional[Mesh], *, fsdp: bool = False,
                 seq_shard: bool = False, rules: Optional[dict] = None):
        self.mesh = mesh
        self.rules = dict(rules or DEFAULT_RULES)
        if not fsdp:
            self.rules["fsdp"] = None
        if not seq_shard:
            self.rules["seq"] = None

    # ------------------------------------------------------------------
    def _axes_for(self, logical: Optional[str], dim: int,
                  used: frozenset = frozenset()) -> Optional[Tuple[str, ...]]:
        if self.mesh is None or logical is None:
            return None
        cand = self.rules.get(logical, None)
        if cand is None:
            return None
        if isinstance(cand, str):
            cand = (cand,)
        axes = tuple(a for a in cand
                     if a in self.mesh.axis_names and a not in used)
        if not axes:
            return None
        extent = math.prod(self.mesh.shape[a] for a in axes)
        if dim % extent != 0:
            # try progressively smaller suffixes (e.g. drop "pod", keep "data")
            for i in range(1, len(axes)):
                sub = axes[i:]
                if dim % math.prod(self.mesh.shape[a] for a in sub) == 0:
                    return sub
            return None
        return axes

    def spec(self, logical: Sequence[Optional[str]], shape: Sequence[int]) -> P:
        assert len(logical) == len(shape), (logical, shape)
        used: set = set()
        parts = []
        for name, dim in zip(logical, shape):
            axes = self._axes_for(name, dim, frozenset(used))
            if axes is None:
                parts.append(None)
                continue
            used.update(axes)
            parts.append(axes if len(axes) > 1 else axes[0])
        return P(*parts)

    def named(self, logical: Sequence[Optional[str]], shape: Sequence[int]) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec(logical, shape))

    def constrain(self, x: jax.Array, logical: Sequence[Optional[str]]) -> jax.Array:
        """with_sharding_constraint if a mesh is active, else identity."""
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.spec(logical, x.shape))
        )


    def extent(self, logical: Optional[str], dim: int) -> int:
        """Number of shards the rules would split a ``dim``-sized axis into."""
        axes = self._axes_for(logical, dim)
        if axes is None:
            return 1
        return math.prod(self.mesh.shape[a] for a in axes)

    def place(self, x: jax.Array, logical: Sequence[Optional[str]]) -> jax.Array:
        """device_put onto the mesh with the resolved sharding (identity off-mesh)."""
        if self.mesh is None:
            return x
        spec = self.spec(logical, x.shape)
        tel = get_telemetry(None)
        if tel.enabled:
            tel.count("shard.placements")
            tel.count("shard.placed_bytes", getattr(x, "nbytes", 0))
            tel.event("shard.place", spec=str(spec),
                      shape=tuple(int(d) for d in x.shape),
                      mesh=dict(self.mesh.shape))
        return jax.device_put(x, NamedSharding(self.mesh, spec))


def null_sharder() -> Sharder:
    return Sharder(None)


# Serving shards only head-like axes.  Everything else stays replicated so the
# only cross-shard merges are all-gathers (pure data movement) — never a psum
# whose float reassociation would break the bitwise token-exactness contract
# with the single-device engine.
SERVING_RULES = {
    "heads": ("model",),
    "kv": ("model",),
}


def parse_mesh(spec: Optional[str]) -> Optional[Mesh]:
    """Build a mesh from an ``AxB`` spec string ("1x8", "2x4", "1x1").

    Two extents map to ("data", "model"); three to ("pod", "data", "model");
    a bare integer to a 1×N ("data", "model") mesh.  ``None``/empty returns
    None (single-device path, no mesh).
    """
    if not spec:
        return None
    extents = tuple(int(p) for p in str(spec).lower().split("x"))
    names = {1: ("data", "model"), 2: ("data", "model"),
             3: ("pod", "data", "model")}[len(extents)]
    if len(extents) == 1:
        extents = (1,) + extents
    n_dev = math.prod(extents)
    if n_dev > len(jax.devices()):
        raise ValueError(
            f"mesh {spec} needs {n_dev} devices, have {len(jax.devices())}")
    return jax.make_mesh(extents, names,
                         axis_types=(AxisType.Auto,) * len(names))


def serving_sharder(mesh: Optional[Mesh]) -> Sharder:
    """Sharder for the serving stack: KV-head partitioning only."""
    return Sharder(mesh, rules=SERVING_RULES)


def param_shardings(sharder: Sharder, axes_tree, shapes_tree):
    """axes tree + eval_shape tree -> tree of NamedSharding (or None)."""
    return jax.tree.map(
        lambda axes, shp: sharder.named(axes, shp.shape),
        axes_tree,
        shapes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x
        ),
    )
