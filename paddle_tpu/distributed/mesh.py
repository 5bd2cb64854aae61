"""Device-mesh topology.

Reference analog: ``CommunicateTopology``/``HybridCommunicateGroup``
(python/paddle/distributed/fleet/base/topology.py:50/:136) — the 4-D hybrid
order ["data", "pipe", "sharding", "model"] (fleet/fleet.py:406) with one
NCCL communicator per axis-group.

TPU-native: a single ``jax.sharding.Mesh`` with named axes. Collectives are
compiler-inserted from sharding annotations; axis groups need no explicit
communicators. Axis order is outermost-first so the innermost axes (tp, sp)
land on the fastest ICI links, mirroring the reference placing "model"
innermost for NVLink locality.
"""

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["SpecLayout", "LAYOUT", "mesh_safe_spec", "HybridTopology",
           "init_mesh", "get_topology", "get_mesh", "set_topology",
           "AXIS_DP", "AXIS_FSDP", "AXIS_TP", "AXIS_PP", "AXIS_SP",
           "AXIS_EP"]

# Canonical axis names (superset of the reference's 4: + sp for
# sequence/context parallelism and ep for expert parallelism, SURVEY §5.7)
AXIS_DP = "dp"          # data parallel (pure replication of params)
AXIS_FSDP = "fsdp"      # sharding axis ≙ reference "sharding" (ZeRO)
AXIS_TP = "tp"          # tensor/model parallel ≙ "model"
AXIS_PP = "pp"          # pipeline parallel ≙ "pipe"
AXIS_SP = "sp"          # sequence/context parallel (new capability)
AXIS_EP = "ep"          # expert parallel

# Outermost → innermost. pp LEADS: on a multi-host device list (host-major
# order) the outermost axis is the one that spans hosts, and pipeline
# stage boundaries move orders of magnitude fewer bytes than dp gradient
# all-reduce — so pp is the axis that can afford DCN (the reference's
# hybrid topology order pp→dp→sharding→mp, fleet/base/topology.py; the
# planner's _axis_tier DCN assignment assumes exactly this order).
_ORDER = ("pp", "dp", "fsdp", "sp", "ep", "tp")

_global_topology = None


@dataclass(frozen=True)
class SpecLayout:
    """Canonical PartitionSpec vocabulary over the hybrid mesh axes — the
    ONE place the repo's sharding conventions are written down (SNIPPETS
    [2] shape, extended with this repo's pp/sp/ep axes). models.gpt and
    models.bert build their PARTITION_RULES from these methods, the
    planner proposes them structurally, and auto_parallel places batches
    with them — so "column parallel" or "vocab embedding" means the same
    spec everywhere, and renaming a mesh axis is a one-line change here.

    Parameter-role methods follow the Megatron TP × ZeRO-3 convention:
    ``column()`` for expanding (d → k·d) weights, ``row()`` for
    contracting ones, with ``fsdp`` always on the non-tp dim so every
    weight is additionally ZeRO-sharded.
    """

    data_axis: str = AXIS_DP
    fsdp_axis: str = AXIS_FSDP
    tp_axis: str = AXIS_TP
    pp_axis: str = AXIS_PP
    sp_axis: str = AXIS_SP
    ep_axis: str = AXIS_EP

    # -- activations --------------------------------------------------------
    @property
    def batch_axes(self) -> Tuple[str, str]:
        """Axes the batch dim splits over (fsdp is ZeRO *data* parallel)."""
        return (self.data_axis, self.fsdp_axis)

    def activation(self, *trailing) -> P:
        """Batch-sharded activation: leading dim over (dp, fsdp), then
        the caller's trailing axes (e.g. ``activation('sp', None)``)."""
        return P(self.batch_axes, *trailing)

    # -- parameter roles ----------------------------------------------------
    def vocab_embedding(self) -> P:      # (V, d) lookup table
        return P(self.tp_axis, self.fsdp_axis)

    def vocab_head(self) -> P:           # (d, V) untied LM head
        return P(self.fsdp_axis, self.tp_axis)

    def vocab_bias(self) -> P:           # (V,) per-vocab bias
        return P(self.tp_axis)

    def position_table(self) -> P:       # (T, d) position/type tables
        return P(None, self.fsdp_axis)

    def column(self) -> P:               # expanding (d, k·d) ≙ megatron col
        return P(self.fsdp_axis, self.tp_axis)

    def column_bias(self) -> P:          # (k·d,) bias of a column layer
        return P(self.tp_axis)

    def row(self) -> P:                  # contracting (k·d, d) ≙ row
        return P(self.tp_axis, self.fsdp_axis)

    def row_bias(self) -> P:             # (d,) model-dim vector: replicate
        return P(None)

    norm = row_bias                      # LN scales/biases replicate too

    def root_linear(self) -> P:          # non-block (d, d') linear: ZeRO rows
        return P(self.fsdp_axis, None)

    def conv_filter(self) -> P:          # OIHW conv: ZeRO over out channels
        return P(self.fsdp_axis)

    def replicated(self) -> P:
        return P()

    # -- expert (MoE) roles -------------------------------------------------
    def expert_column(self) -> P:        # (E, d, k·d)
        return P(self.ep_axis, self.fsdp_axis, self.tp_axis)

    def expert_column_bias(self) -> P:   # (E, 1, k·d)
        return P(self.ep_axis, None, self.tp_axis)

    def expert_row(self) -> P:           # (E, k·d, d)
        return P(self.ep_axis, self.tp_axis, self.fsdp_axis)

    def expert_row_bias(self) -> P:      # (E, 1, d)
        return P(self.ep_axis, None, None)

    # -- derived layouts ----------------------------------------------------
    def stacked(self, spec: P, ndim: Optional[int] = None) -> P:
        """Scan-stacked variant of a per-block param spec: a leading
        REPLICATED layer axis ahead of the block rules, truncated when
        the leading axis consumed the rank budget (``ndim`` = rank of
        the stacked leaf). The layer axis itself never shards — scan
        slices it — so the per-layer fsdp/tp sharding is preserved
        verbatim on the trailing dims."""
        t = tuple(spec)
        if ndim is not None and len(t) >= ndim:
            t = t[:ndim - 1]
        return P(None, *t)

    def pipeline_stacked(self, spec: P, n_virtual: int = 1) -> P:
        """Pipeline-stacked param: (S, lps, ...) with the stage axis on
        'pp' — or (V, S, lpg, ...) interleaved, where only S shards."""
        lead = ((self.pp_axis, None) if n_virtual == 1
                else (None, self.pp_axis, None))
        return P(*(lead + tuple(spec)))


# The default layout instance every consumer shares. Axis names match the
# mesh built by init_mesh; a custom topology would install its own.
LAYOUT = SpecLayout()


def mesh_safe_spec(spec: P, mesh) -> P:
    """Drop axes the mesh does not define (e.g. 'fsdp' on a bare
    ('tp',) Mesh) — the spec then replicates over the missing axis
    instead of NamedSharding raising."""
    names = set(mesh.axis_names)

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, tuple):
            kept = tuple(a for a in entry if a in names)
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return entry if entry in names else None

    return P(*(keep(a) for a in tuple(spec)))


@dataclass
class HybridTopology:
    """≙ HybridCommunicateGroup: holds the Mesh plus per-axis degrees."""

    mesh: Mesh
    degrees: Dict[str, int]

    # -- reference-parity accessors (topology.py:136 surface) -----------------
    def get_data_parallel_world_size(self):
        return self.degrees.get("dp", 1) * self.degrees.get("fsdp", 1)

    def get_model_parallel_world_size(self):
        return self.degrees.get("tp", 1)

    def get_pipe_parallel_world_size(self):
        return self.degrees.get("pp", 1)

    def get_sharding_parallel_world_size(self):
        return self.degrees.get("fsdp", 1)

    def get_sequence_parallel_world_size(self):
        return self.degrees.get("sp", 1)

    def get_expert_parallel_world_size(self):
        return self.degrees.get("ep", 1)

    @property
    def axis_names(self):
        return self.mesh.axis_names

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())


def init_mesh(dp: int = 1, tp: int = 1, pp: int = 1, fsdp: int = 1,
              sp: int = 1, ep: int = 1,
              devices: Optional[Sequence] = None,
              set_global: bool = True) -> HybridTopology:
    """Build the hybrid mesh (≙ fleet.init(strategy.hybrid_configs)).

    Degrees of 1 are kept as size-1 mesh axes so sharding specs can always
    name every axis; XLA elides trivial axes at compile time.
    """
    devices = list(devices if devices is not None else jax.devices())
    degrees = {"dp": dp, "pp": pp, "fsdp": fsdp, "sp": sp, "ep": ep,
               "tp": tp}
    total = int(np.prod(list(degrees.values())))
    if total != len(devices):
        raise ValueError(
            f"mesh degrees {degrees} (= {total}) != device count "
            f"{len(devices)}")
    shape = tuple(degrees[a] for a in _ORDER)
    arr = np.asarray(devices).reshape(shape)
    mesh = Mesh(arr, _ORDER)
    topo = HybridTopology(mesh=mesh, degrees=degrees)
    if set_global:
        global _global_topology
        _global_topology = topo
    return topo


def get_topology() -> Optional[HybridTopology]:
    return _global_topology


def get_mesh() -> Optional[Mesh]:
    return _global_topology.mesh if _global_topology else None


def gspmd_partitioned() -> bool:
    """True when the code being traced will be partitioned by GSPMD
    over more than one device: a multi-device global mesh is set and
    the trace is not inside a shard_map body that has taken every
    nontrivial axis manual. A Pallas (Mosaic) custom call has no
    partitioning rule, so kernels are legal exactly where this is
    False."""
    mesh = get_mesh()
    if mesh is None or mesh.size == 1:
        return False
    manual = jax.sharding.get_abstract_mesh().manual_axes
    return any(mesh.shape[a] > 1 and a not in manual
               for a in mesh.axis_names)


def set_topology(topo: HybridTopology):
    global _global_topology
    _global_topology = topo
